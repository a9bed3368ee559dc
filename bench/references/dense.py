"""The dense decoder family: a plain decoder of grouped-query attention
and MLP layers (Nemotron, Qwen2).  Its plain reference, its work counts,
and the published keys the program's configuration must match.

**A reference family** is one file under ``bench/references/``, named
by a configuration file's ``"reference"`` key and loaded by path
(``bench/model.py``).  The harness and the metric readers call it
through this interface and nothing else, so a new model family is a new
file, not an edit:

* ``dims_of(cfgfile)``: the family's sizes from the configuration
  file's published keys, a hashable value that the harness hands back
  to the functions below (``run.Context.dims``);
* ``check_program(cfgfile, cfg)``: raises ``ValueError`` on every key
  in which the program's ``ModelConfig`` differs from the published
  keys, and on any mechanism the family has no reference for;
* ``logits_at(seed, cfgfile, sequences, positions, *, precision)``:
  the logits of each token sequence at its positions, from weights the
  family draws from the seed itself; ``precision="fp8"`` is the
  control;
* ``prefill(dims, b, s)`` and ``decode(dims, b, pos)``: the ``Work``
  (``bench/work.py``) of a whole prefill or decode step;
* one work function for each kernel a roofline reader reads, counted
  over the layers that kernel runs in: here ``flash_attention(dims, b,
  s)`` and ``decode_attention(dims, b, pos)``, in every layer.  A
  hybrid family counts attention only in its attention layers.

**The reference** reads only the published keys of a configuration file
(Hugging Face ``config.json`` names) and the seed.  It imports nothing
of the program and takes nothing the program made: it draws its own
weights from the seed by the rule the configuration file's ``init``
group states (the program's rule: a key tree split from
``PRNGKey(seed)``, embeddings ``N(0, 0.02²)``, projections
truncated-normal in ``[-2, 2]`` over ``sqrt(fan_in)``, drawn in the
served dtype; norms identity, biases 0), and computes the forward pass
in float32 at ``highest`` matmul precision, one layer at a time for all
rows.  Attention runs ``Q_BLOCK`` queries at a time; each query's
softmax runs over all of its keys at once, so the arithmetic per query
is that of the whole score matrix and only the peak memory is smaller.

Departures from the published descriptions, each noted here:

* RoPE rotates interleaved pairs ``(x[2i], x[2i+1])`` (the program's
  pairing), where Hugging Face rotates halves.  With weights drawn
  i.i.d. the two differ by a fixed permutation of the rotated q/k
  columns.
* Nemotron's LayerNorm1p scales by ``1 + w`` with ``w`` drawn as 0; the
  reference uses a plain LayerNorm with scale 1, the same function.

``precision="fp8"`` is the control: the same forward with the operands
of every matrix product quantized to float8 e4m3 (per-tensor scale for
weights, per-row scale for activations) and accumulated in float32.

**The work counts** count the work the *operation* needs, whatever
implements it, so a later change of kernel or fusion is measured
against the same work:

* a whole prefill step: every projection of every layer for ``b × S``
  tokens, causal attention over the lower triangle, the head for each
  sequence's last position; bytes are the weights read once, the K/V
  written for ``S`` positions and the logits;
* a whole decode step at write position ``p``: the projections for ``b``
  tokens, attention over ``p + 1`` cached positions, the head for every
  row; bytes are the weights, the K/V read at ``p + 1`` positions and
  written at one, and the logits;
* the attention kernels alone: the same attention work, with the bytes
  of q, k, v read and the output written.

Weights and caches are in the served dtype; logits are float32.
"""

from __future__ import annotations

import math
from dataclasses import dataclass
from typing import Dict, List, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from bench.work import Work

NEG_INF = -1e30
Q_BLOCK = 512            # queries per block of the reference's attention
LOGIT_BYTES = 4


@dataclass(frozen=True)
class Dims:
    d: int
    heads: int
    kv_heads: int
    head_dim: int
    ff: int
    vocab: int
    layers: int
    rope_theta: float
    rope_pct: float
    norm: str            # "layernorm" | "rmsnorm"
    eps: float
    gated: bool          # SwiGLU (silu) vs squared-ReLU MLP
    qkv_bias: bool
    tied: bool
    dtype: str


def dims_of(published: Dict) -> Dims:
    """The sizes the reference needs, from a configuration's published
    keys (a ``llm_config`` group when the model wraps a language model)."""
    p = published.get("llm_config", published)
    kind = p["model_type"]
    if kind == "nemotron":
        norm, eps, qkv_bias = "layernorm", p["norm_eps"], False
    elif kind == "qwen2":
        norm, eps, qkv_bias = "rmsnorm", p["rms_norm_eps"], True
    else:
        raise ValueError(f"no reference for model_type {kind!r}")
    act = p["hidden_act"]
    if act not in ("relu2", "silu"):
        raise ValueError(f"no reference for hidden_act {act!r}")
    heads = p["num_attention_heads"]
    return Dims(
        d=p["hidden_size"], heads=heads, kv_heads=p["num_key_value_heads"],
        head_dim=p.get("head_dim") or p["hidden_size"] // heads,
        ff=p["intermediate_size"], vocab=p["vocab_size"],
        layers=p["num_hidden_layers"], rope_theta=float(p["rope_theta"]),
        rope_pct=float(p.get("partial_rotary_factor", 1.0)), norm=norm,
        eps=float(eps), gated=act == "silu", qkv_bias=qkv_bias,
        tied=bool(p["tie_word_embeddings"]), dtype=p["torch_dtype"])


def check_program(published: Dict, cfg) -> None:
    """The program's configuration against the published keys, key by
    key, so the program and the reference cannot be handed two
    different models; this family has no reference for a layer pattern
    other than attention alone, for experts, or for another
    activation."""
    from repro.configs.base import ATTN
    dm = dims_of(published)
    want = {
        "d_model": dm.d, "n_heads": dm.heads, "n_kv_heads": dm.kv_heads,
        "head_dim": dm.head_dim, "d_ff": dm.ff, "vocab_size": dm.vocab,
        "n_layers": dm.layers, "rope_theta": dm.rope_theta,
        "rope_pct": dm.rope_pct, "norm": dm.norm, "norm_eps": dm.eps,
        "gated": dm.gated, "qkv_bias": dm.qkv_bias,
        "tie_embeddings": dm.tied, "dtype": dm.dtype,
        "pattern": (ATTN,), "extras": (False, False, False, 0.0, (), ()),
    }
    have = {
        "d_model": cfg.d_model, "n_heads": cfg.n_heads,
        "n_kv_heads": cfg.n_kv_heads, "head_dim": cfg.resolved_head_dim,
        "d_ff": cfg.d_ff, "vocab_size": cfg.vocab_size,
        "n_layers": cfg.n_layers, "rope_theta": float(cfg.rope_theta),
        "rope_pct": float(cfg.rope_pct), "norm": cfg.norm,
        "norm_eps": float(cfg.norm_eps), "gated": cfg.act == "silu",
        "qkv_bias": cfg.qkv_bias, "tie_embeddings": cfg.tie_embeddings,
        "dtype": cfg.dtype, "pattern": tuple(cfg.pattern),
        "extras": (cfg.qk_norm, cfg.post_norms, cfg.scale_embedding,
                   float(cfg.logit_softcap), cfg.prefix, cfg.suffix),
    }
    if cfg.act not in ("silu", "relu2") or cfg.moe is not None:
        raise ValueError(f"{cfg.name}: no reference for act {cfg.act!r}"
                         f" or experts")
    bad = {k: (have[k], want[k]) for k in want if have[k] != want[k]}
    if bad:
        raise ValueError(f"program config {cfg.name} differs from the "
                         f"published keys: {bad}")


# --------------------------------------------------------------------- #
# weights from the seed
# --------------------------------------------------------------------- #
def _dense(key, shape, fan_in, dtype):
    return (1.0 / math.sqrt(fan_in)) * jax.random.truncated_normal(
        key, -2.0, 2.0, shape, dtype)


def _top_keys(seed: int):
    return jax.random.split(jax.random.PRNGKey(seed), 8)


def _layer_keys(seed: int, n_layers: int):
    (position_key,) = jax.random.split(_top_keys(seed)[6], 1)
    return jax.random.split(position_key, n_layers)


def layer_weights(key, dm: Dims) -> Dict[str, jax.Array]:
    """One decoder layer's projections, in the served dtype."""
    dt = jnp.dtype(dm.dtype)
    ks = jax.random.split(key, 6)
    ka = jax.random.split(ks[1], 4)
    km = jax.random.split(ks[3], 3)
    d, H, Hk, D, F = dm.d, dm.heads, dm.kv_heads, dm.head_dim, dm.ff
    w = {
        "wq": _dense(ka[0], (d, H, D), d, dt),
        "wk": _dense(ka[1], (d, Hk, D), d, dt),
        "wv": _dense(ka[2], (d, Hk, D), d, dt),
        "wo": _dense(ka[3], (H, D, d), H * D, dt),
        "down": _dense(km[1], (F, d), F, dt),
    }
    if dm.gated:
        w["gate"] = _dense(km[0], (d, F), d, dt)
        w["up"] = _dense(km[2], (d, F), d, dt)
    else:
        w["up"] = _dense(km[0], (d, F), d, dt)
    return w


def embedding(seed: int, dm: Dims):
    return jax.random.normal(_top_keys(seed)[0], (dm.vocab, dm.d),
                             jnp.dtype(dm.dtype)) * 0.02


def head(seed: int, dm: Dims):
    """(d, V) output projection: the embedding's transpose when tied."""
    if dm.tied:
        return embedding(seed, dm).T
    return jax.random.normal(_top_keys(seed)[2], (dm.d, dm.vocab),
                             jnp.dtype(dm.dtype)) * 0.02


# --------------------------------------------------------------------- #
# forward
# --------------------------------------------------------------------- #
def _fp8(x, axis):
    """Quantize to float8 e4m3 with a scale per slice along ``axis``
    (None: one scale for the tensor), and back to float32."""
    amax = jnp.max(jnp.abs(x), axis=axis, keepdims=axis is not None)
    scale = jnp.where(amax > 0, 448.0 / amax, 1.0)
    q = (x * scale).astype(jnp.float8_e4m3fn).astype(jnp.float32)
    return q / scale


def _mm(x, w, precision: str, spec: str):
    """``einsum(spec, x, w)`` in float32, or with fp8 operands."""
    x = x.astype(jnp.float32)
    w = w.astype(jnp.float32)
    if precision == "fp8":
        x = _fp8(x, -1)
        w = _fp8(w, None)
    return jnp.einsum(spec, x, w, precision=jax.lax.Precision.HIGHEST)


def _norm(x, dm: Dims):
    if dm.norm == "layernorm":
        mu = jnp.mean(x, -1, keepdims=True)
        var = jnp.mean(jnp.square(x - mu), -1, keepdims=True)
        return (x - mu) * jax.lax.rsqrt(var + dm.eps)
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True)
                             + dm.eps)


def _rope(x, dm: Dims):
    """x: (R, S, H, D), positions 0..S-1, interleaved pairs."""
    S, D = x.shape[1], x.shape[-1]
    rot = int(D * dm.rope_pct)
    rot -= rot % 2
    inv = 1.0 / (dm.rope_theta ** (np.arange(0, rot, 2) / rot))
    ang = np.arange(S)[:, None] * inv[None, :]
    cos = jnp.asarray(np.cos(ang), jnp.float32)[None, :, None, :]
    sin = jnp.asarray(np.sin(ang), jnp.float32)[None, :, None, :]
    xr, xp = x[..., :rot], x[..., rot:]
    x1, x2 = xr[..., 0::2], xr[..., 1::2]
    r = jnp.stack([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)
    return jnp.concatenate([r.reshape(xr.shape), xp], -1)


def q_block(S: int) -> int:
    """Queries per attention block: ``Q_BLOCK``, or the largest power of
    two under it that divides ``S`` (a multiple of 128)."""
    blk = Q_BLOCK
    while S % blk:
        blk //= 2
    return blk


def attention(q, k, v, precision: str, block: int):
    """Causal attention of (R, S, H, D) queries over keys and values of
    the same shape, ``block`` queries at a time: each block's scores
    span all ``S`` keys, the ones after a query masked, so every query's
    softmax is the one the whole (S, S) score matrix gives."""
    R, S, H, D = q.shape
    n = S // block
    keys = jnp.arange(S)
    blocks = q.reshape(R, n, block, H, D).swapaxes(0, 1)

    def one(args):
        i, qi = args
        s = _mm(qi, k, precision, "rqhd,rkhd->rhqk") / math.sqrt(D)
        rows = i * block + jnp.arange(block)
        s = jnp.where(keys[None, :] <= rows[:, None], s, NEG_INF)
        p = jax.nn.softmax(s, axis=-1)
        return _mm(p, v, precision, "rhqk,rkhd->rqhd")

    out = jax.lax.map(one, (jnp.arange(n), blocks))
    return out.swapaxes(0, 1).reshape(R, S, H, D)


def _layer(x, w, dm: Dims, precision: str):
    h = _norm(x, dm)
    q = _mm(h, w["wq"], precision, "rsd,dhk->rshk")
    k = _mm(h, w["wk"], precision, "rsd,dhk->rshk")
    v = _mm(h, w["wv"], precision, "rsd,dhk->rshk")
    q, k = _rope(q, dm), _rope(k, dm)
    rep = dm.heads // dm.kv_heads
    k = jnp.repeat(k, rep, axis=2)
    v = jnp.repeat(v, rep, axis=2)
    a = attention(q, k, v, precision, q_block(x.shape[1]))
    x = x + _mm(a, w["wo"], precision, "rshk,hkd->rsd")
    h = _norm(x, dm)
    if dm.gated:
        u = jax.nn.silu(_mm(h, w["gate"], precision, "rsd,df->rsf")) \
            * _mm(h, w["up"], precision, "rsd,df->rsf")
    else:
        u = jnp.square(jax.nn.relu(_mm(h, w["up"], precision, "rsd,df->rsf")))
    return x + _mm(u, w["down"], precision, "rsf,fd->rsd")


def logits_at(seed: int, published: Dict, sequences: Sequence[np.ndarray],
              positions: Sequence[Sequence[int]], *,
              precision: str = "f32", row_chunk: int = 6,
              vocab_chunk: int = 32768) -> List[np.ndarray]:
    """Logits of each token sequence at its ``positions``: a list of
    (len(positions[r]), V) float32 arrays.  Rows of like length go
    through the layers together, ``row_chunk`` at a time, each layer's
    weights drawn once."""
    dm = dims_of(published)
    order = sorted(range(len(sequences)), key=lambda r: len(sequences[r]))
    chunks = [order[i:i + row_chunk] for i in range(0, len(order), row_chunk)]
    with jax.default_matmul_precision("highest"):
        emb = embedding(seed, dm)
        xs = []
        for chunk in chunks:
            S = -(-max(len(sequences[r]) for r in chunk) // 128) * 128
            toks = np.zeros((len(chunk), S), np.int32)
            for j, r in enumerate(chunk):
                toks[j, :len(sequences[r])] = sequences[r]
            xs.append(emb[jnp.asarray(toks)].astype(jnp.float32))
        del emb
        keys = _layer_keys(seed, dm.layers)
        layer = jax.jit(_layer, static_argnums=(2, 3))
        make = jax.jit(layer_weights, static_argnums=(1,))
        for i in range(dm.layers):
            w = make(keys[i], dm)
            xs = [layer(x, w, dm, precision) for x in xs]
            del w
        hidden = {}
        for chunk, x in zip(chunks, xs):
            x = _norm(x, dm)
            for j, r in enumerate(chunk):
                hidden[r] = x[j, np.asarray(positions[r])]
        del xs
        hw = head(seed, dm)
        out = []
        for r in range(len(sequences)):
            parts = [np.asarray(_mm(hidden[r], hw[:, c:c + vocab_chunk],
                                    precision, "pd,dv->pv"))
                     for c in range(0, dm.vocab, vocab_chunk)]
            out.append(np.concatenate(parts, axis=-1))
    return out


# --------------------------------------------------------------------- #
# work counts
# --------------------------------------------------------------------- #
def _itemsize(dm: Dims) -> int:
    return {"bfloat16": 2, "float16": 2, "float32": 4}[dm.dtype]


def layer_params(dm: Dims) -> int:
    attn = dm.d * (dm.heads + 2 * dm.kv_heads) * dm.head_dim \
        + dm.heads * dm.head_dim * dm.d
    mlp = (3 if dm.gated else 2) * dm.d * dm.ff
    return attn + mlp


def weight_bytes(dm: Dims) -> float:
    """Layers and head as one step reads them (a tied head is the
    embedding, read once)."""
    return (dm.layers * layer_params(dm) + dm.d * dm.vocab) * _itemsize(dm)


def kv_bytes_per_position(dm: Dims) -> float:
    """K and V of one position of one sequence, all layers."""
    return dm.layers * 2 * dm.kv_heads * dm.head_dim * _itemsize(dm)


def attention_work(dm: Dims, b: int, q_len: int, kv_from: int) -> Work:
    """Causal attention of ``q_len`` queries at positions
    ``kv_from .. kv_from + q_len - 1`` over every key at or before each,
    for all layers: QK^T and PV, 2 FLOPs per multiply-add."""
    keys = q_len * kv_from + q_len * (q_len + 1) // 2
    flops = 4.0 * b * dm.heads * dm.head_dim * keys * dm.layers
    it = _itemsize(dm)
    q_o = 2 * b * q_len * dm.heads * dm.head_dim * it
    kv = 2 * b * (kv_from + q_len) * dm.kv_heads * dm.head_dim * it
    return Work(flops, float((q_o + kv) * dm.layers))


def prefill(dm: Dims, b: int, s: int) -> Work:
    dense = 2.0 * b * s * dm.layers * layer_params(dm) + 2.0 * b * dm.d * dm.vocab
    att = attention_work(dm, b, s, 0)
    byt = weight_bytes(dm) + b * s * kv_bytes_per_position(dm) \
        + b * dm.vocab * LOGIT_BYTES
    return Work(dense + att.flops, float(byt))


def decode(dm: Dims, b: int, pos: int) -> Work:
    dense = 2.0 * b * (dm.layers * layer_params(dm) + dm.d * dm.vocab)
    att = attention_work(dm, b, 1, pos)
    byt = weight_bytes(dm) + b * (pos + 2) * kv_bytes_per_position(dm) \
        + b * dm.vocab * LOGIT_BYTES
    return Work(dense + att.flops, float(byt))


def flash_attention(dm: Dims, b: int, s: int) -> Work:
    return attention_work(dm, b, s, 0)


def decode_attention(dm: Dims, b: int, pos: int) -> Work:
    return attention_work(dm, b, 1, pos)


__all__ = ["Dims", "attention", "attention_work", "check_program",
           "decode", "decode_attention", "dims_of", "embedding",
           "flash_attention", "head", "kv_bytes_per_position",
           "layer_params", "layer_weights", "logits_at", "prefill",
           "q_block", "weight_bytes"]
