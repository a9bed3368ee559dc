"""Plain reference of the served decoders, for the check that decides
``correct``.

It reads only the published keys of a configuration file (Hugging Face
``config.json`` names) and the seed.  It imports nothing of the program
and takes nothing the program made: it draws its own weights from the
seed by the rule the configuration file's ``init`` group states (the
program's rule: a key tree split from ``PRNGKey(seed)``, embeddings
``N(0, 0.02²)``, projections truncated-normal in ``[-2, 2]`` over
``sqrt(fan_in)``, drawn in the served dtype; norms identity, biases 0),
and computes the forward pass in float32 at ``highest`` matmul
precision, one layer at a time for all rows.

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
"""

from __future__ import annotations

import math
from dataclasses import dataclass
from typing import Dict, List, Sequence

import jax
import jax.numpy as jnp
import numpy as np

NEG_INF = -1e30


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


def _layer(x, w, dm: Dims, precision: str):
    R, S, _ = x.shape
    h = _norm(x, dm)
    q = _mm(h, w["wq"], precision, "rsd,dhk->rshk")
    k = _mm(h, w["wk"], precision, "rsd,dhk->rshk")
    v = _mm(h, w["wv"], precision, "rsd,dhk->rshk")
    q, k = _rope(q, dm), _rope(k, dm)
    rep = dm.heads // dm.kv_heads
    k = jnp.repeat(k, rep, axis=2)
    v = jnp.repeat(v, rep, axis=2)
    s = _mm(q, k, precision, "rqhd,rkhd->rhqk") / math.sqrt(dm.head_dim)
    causal = np.tril(np.ones((S, S), bool))
    s = jnp.where(causal[None, None], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    a = _mm(p, v, precision, "rhqk,rkhd->rqhd")
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


__all__ = ["Dims", "dims_of", "embedding", "head", "layer_weights",
           "logits_at"]
