"""Shared model components: norms, RoPE, MLPs, attention, loss.

Attention is implemented in the *flash pattern* even in pure jnp — a
Python loop over query tiles with an inner ``lax.scan`` over KV tiles and
an online-softmax accumulator.  The compiled HLO therefore has the memory
profile of the TPU target algorithm (no S×S score materialization), so
dry-run roofline terms reflect the system we would actually deploy; the
Pallas kernels in repro.kernels are drop-in tilings of the same math.
Causal tiling only visits KV tiles at-or-before each query tile and
sliding-window tiling only visits tiles inside the window, so HLO FLOPs
match the algorithmic cost instead of double-counting masked work.
"""

from __future__ import annotations

import dataclasses
import functools
import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

# ----------------------------------------------------------------------- #
# sharding hints (no-ops outside a mesh context)
# ----------------------------------------------------------------------- #
def _ambient_axes():
    try:
        mesh = jax.sharding.get_abstract_mesh()
    except Exception:  # noqa: BLE001
        return ()
    if mesh is None or not getattr(mesh, "axis_names", ()):
        return ()
    return tuple(mesh.axis_names), dict(mesh.shape)


def shard_seq(x, *, batch_dim: int = 0, seq_dim: int = 1):
    """Megatron-SP constraint: shard the sequence dim over "model".

    Activations between blocks are (B, S, d); constraining S over the
    model axis makes XLA run norms/MLP column-sections sequence-sharded
    and insert all-gather/reduce-scatter pairs around attention instead
    of replicating activations model-axis-wide.  No-op when no mesh is
    ambient (unit tests, single-device runs) or dims are indivisible.
    """
    info = _ambient_axes()
    if not info:
        return x
    names, sizes = info
    if "model" not in names or x.shape[seq_dim] % sizes["model"]:
        return x
    from jax.sharding import PartitionSpec as P
    batch_axes = tuple(a for a in ("pod", "data") if a in names)
    prod = 1
    for a in batch_axes:
        prod *= sizes[a]
    spec = [None] * x.ndim
    if batch_axes and x.shape[batch_dim] % prod == 0:
        spec[batch_dim] = batch_axes
    spec[seq_dim] = "model"
    return jax.lax.with_sharding_constraint(x, P(*spec))


def shard_heads(x, *, head_dim: int = 2):
    """Pre-attention Megatron-SP constraint: full sequence, heads sharded.

    Under sequence parallelism q/k/v must be gathered over seq *once* per
    layer; without this constraint the blocked-attention KV tile loop's
    dynamic slices each trigger a full all-gather of K/V (observed:
    640 GiB/layer on deepseek-v3 prefill — EXPERIMENTS.md §Perf).
    Heads shard over "model" when divisible; otherwise they replicate
    (e.g. 8 KV heads on a 16-way axis), which is still correct SP.
    """
    info = _ambient_axes()
    if not info:
        return x
    names, sizes = info
    if "model" not in names:
        return x
    from jax.sharding import PartitionSpec as P
    batch_axes = tuple(a for a in ("pod", "data") if a in names)
    prod = 1
    for a in batch_axes:
        prod *= sizes[a]
    spec = [None] * x.ndim
    if batch_axes and x.shape[0] % prod == 0:
        spec[0] = batch_axes
    if x.shape[head_dim] % sizes["model"] == 0:
        spec[head_dim] = "model"
    return jax.lax.with_sharding_constraint(x, P(*spec))


def shard_decode_scores(s):
    """Keep decode attention scores sharded on the cache-length dim.

    s: (B, H, 1, S).  Without this constraint XLA may reshard the whole
    KV cache onto attention heads ("involuntary full rematerialization"),
    turning one decode step into a cache-sized collective.
    """
    info = _ambient_axes()
    if not info:
        return s
    names, sizes = info
    if "model" not in names or s.shape[-1] % sizes["model"]:
        return s
    from jax.sharding import PartitionSpec as P
    batch_axes = tuple(a for a in ("pod", "data") if a in names)
    prod = 1
    for a in batch_axes:
        prod *= sizes[a]
    lead = batch_axes if batch_axes and s.shape[0] % prod == 0 else None
    return jax.lax.with_sharding_constraint(
        s, P(lead, None, None, "model"))


# ----------------------------------------------------------------------- #
# initializers
# ----------------------------------------------------------------------- #
def dense_init(rng, shape, in_axis: int = 0, dtype=jnp.float32):
    """Truncated-normal fan-in init (LeCun-style)."""
    fan_in = shape[in_axis] if isinstance(in_axis, int) else int(
        np.prod([shape[a] for a in in_axis]))
    std = 1.0 / math.sqrt(max(1, fan_in))
    return std * jax.random.truncated_normal(rng, -2.0, 2.0, shape, dtype)


def embed_init(rng, shape, dtype=jnp.float32):
    return jax.random.normal(rng, shape, dtype) * 0.02


# ----------------------------------------------------------------------- #
# norms
# ----------------------------------------------------------------------- #
def rms_norm(x, weight, eps: float):
    dtype = x.dtype
    x = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    out = x * jax.lax.rsqrt(var + eps)
    return (out * (1.0 + weight.astype(jnp.float32))).astype(dtype)


def layer_norm(x, weight, bias, eps: float):
    dtype = x.dtype
    x = x.astype(jnp.float32)
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.var(x, axis=-1, keepdims=True)
    out = (x - mean) * jax.lax.rsqrt(var + eps)
    return (out * weight.astype(jnp.float32)
            + bias.astype(jnp.float32)).astype(dtype)


def init_norm(rng, d: int, kind: str):
    del rng
    if kind == "rmsnorm":
        return {"scale": jnp.zeros((d,), jnp.float32)}
    return {"scale": jnp.ones((d,), jnp.float32),
            "bias": jnp.zeros((d,), jnp.float32)}


def apply_norm(params, x, kind: str, eps: float):
    if kind == "rmsnorm":
        return rms_norm(x, params["scale"], eps)
    return layer_norm(x, params["scale"], params["bias"], eps)


# ----------------------------------------------------------------------- #
# rotary position embeddings
# ----------------------------------------------------------------------- #
def rope_frequencies(head_dim: int, theta: float, rope_pct: float = 1.0
                     ) -> Tuple[int, jnp.ndarray]:
    """Number of rotated dims (even) and their inverse frequencies."""
    rot = int(head_dim * rope_pct)
    rot -= rot % 2
    inv = 1.0 / (theta ** (jnp.arange(0, rot, 2, dtype=jnp.float32) / rot))
    return rot, inv


def apply_rope(x, positions, theta: float, rope_pct: float = 1.0):
    """x: (..., S, H, D) or (..., S, D); positions: (..., S)."""
    head_dim = x.shape[-1]
    rot, inv = rope_frequencies(head_dim, theta, rope_pct)
    if rot == 0:
        return x
    angles = positions[..., :, None].astype(jnp.float32) * inv  # (..., S, rot/2)
    if x.ndim == angles.ndim + 1:          # (..., S, H, D): broadcast over heads
        angles = angles[..., None, :]
    cos = jnp.cos(angles)
    sin = jnp.sin(angles)
    x_rot, x_pass = x[..., :rot], x[..., rot:]
    x1, x2 = x_rot[..., ::2], x_rot[..., 1::2]
    r1 = x1 * cos - x2 * sin
    r2 = x2 * cos + x1 * sin
    rotated = jnp.stack([r1, r2], axis=-1).reshape(x_rot.shape)
    return jnp.concatenate([rotated.astype(x.dtype), x_pass], axis=-1)


# ----------------------------------------------------------------------- #
# MLPs
# ----------------------------------------------------------------------- #
_ACTS = {
    "silu": jax.nn.silu,
    "gelu": functools.partial(jax.nn.gelu, approximate=True),
    "relu": jax.nn.relu,
    "relu2": lambda x: jnp.square(jax.nn.relu(x)),
}


def init_mlp(rng, d_model: int, d_ff: int, *, gated: bool, dtype):
    k1, k2, k3 = jax.random.split(rng, 3)
    p = {"down": dense_init(k2, (d_ff, d_model), dtype=dtype)}
    if gated:
        p["gate"] = dense_init(k1, (d_model, d_ff), dtype=dtype)
        p["up"] = dense_init(k3, (d_model, d_ff), dtype=dtype)
    else:
        p["up"] = dense_init(k1, (d_model, d_ff), dtype=dtype)
    return p


def apply_mlp(params, x, act: str, *, gated: bool):
    fn = _ACTS[act]
    if gated:
        h = fn(x @ params["gate"]) * (x @ params["up"])
    else:
        h = fn(x @ params["up"])
    return h @ params["down"]


# ----------------------------------------------------------------------- #
# attention — flash-pattern tiled softmax in jnp
# ----------------------------------------------------------------------- #
NEG_INF = -0.7 * float(np.finfo(np.float32).max)


def _attend_tile(q, k, v, scale, bias):
    """One (q-tile × kv-tile) step: returns (scores_max, exp_scores@v, sumexp)."""
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                   preferred_element_type=jnp.float32) * scale
    if bias is not None:
        s = s + bias
    m = jnp.max(s, axis=-1)
    p = jnp.exp(s - m[..., None])
    o = jnp.einsum("bhqk,bkhd->bqhd", p.astype(v.dtype), v,
                   preferred_element_type=jnp.float32)
    return m, o, jnp.sum(p, axis=-1)


def _repeat_kv(k, n_rep: int):
    if n_rep == 1:
        return k
    return jnp.repeat(k, n_rep, axis=2)


def naive_attention(q, k, v, *, causal: bool, window: int = 0,
                    q_offset: int = 0, kv_len: Optional[jnp.ndarray] = None):
    """Reference attention (materializes scores). q:(B,Sq,H,D) k/v:(B,Sk,Hkv,D).

    ``q_offset`` is the absolute position of q[0] (for decode/windows).
    ``kv_len`` optionally masks cache positions >= kv_len (decode).
    """
    B, Sq, H, D = q.shape
    Sk, Hkv = k.shape[1], k.shape[2]
    k = _repeat_kv(k, H // Hkv)
    v = _repeat_kv(v, H // Hkv)
    scale = 1.0 / math.sqrt(D)
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                   preferred_element_type=jnp.float32) * scale
    qpos = q_offset + jnp.arange(Sq)[:, None]
    kpos = jnp.arange(Sk)[None, :]
    mask = jnp.ones((Sq, Sk), bool)
    if causal:
        mask &= kpos <= qpos
    if window:
        mask &= kpos > qpos - window
    if kv_len is not None:
        mask &= kpos < kv_len
    s = jnp.where(mask[None, None], s, NEG_INF)
    p = jax.nn.softmax(s.astype(jnp.float32), axis=-1)
    out = jnp.einsum("bhqk,bkhd->bqhd", p.astype(v.dtype), v)
    return out.astype(q.dtype)


def blocked_attention(q, k, v, *, causal: bool, window: int = 0,
                      block_q: int = 512, block_kv: int = 1024):
    """Flash-pattern attention: online softmax over KV tiles.

    Only tiles that can contain unmasked entries are visited: causal
    attention does ~half the FLOPs of the dense score matrix and window
    attention does O(S·w).  Falls back to :func:`naive_attention` when the
    sequence is smaller than one tile.
    """
    B, Sq, H, D = q.shape
    Sk, Hkv = k.shape[1], k.shape[2]
    if Sq <= block_q or Sk <= block_kv or Sq % block_q or Sk % block_kv:
        # small or tile-misaligned sequences take the exact path (the
        # production shapes are all tile multiples)
        return naive_attention(q, k, v, causal=causal, window=window)
    n_rep = H // Hkv
    scale = 1.0 / math.sqrt(D)
    kr = _repeat_kv(k, n_rep)
    vr = _repeat_kv(v, n_rep)
    kv_tiles = Sk // block_kv

    outs = []
    for qi in range(Sq // block_q):
        q_blk = q[:, qi * block_q:(qi + 1) * block_q]
        q_lo, q_hi = qi * block_q, (qi + 1) * block_q
        # static KV tile range for this query tile
        lo_tile = 0
        hi_tile = kv_tiles
        if causal:
            hi_tile = min(kv_tiles, (q_hi + block_kv - 1) // block_kv)
        if window:
            lo_tile = max(0, (q_lo - window) // block_kv)
        n_tiles = hi_tile - lo_tile

        def kv_step(carry, ki):
            m_prev, o_prev, l_prev = carry
            start = lo_tile * block_kv + ki * block_kv
            k_blk = jax.lax.dynamic_slice_in_dim(kr, start, block_kv, axis=1)
            v_blk = jax.lax.dynamic_slice_in_dim(vr, start, block_kv, axis=1)
            bias = None
            if causal or window:
                qpos = q_lo + jnp.arange(block_q)[:, None]
                kpos = start + jnp.arange(block_kv)[None, :]
                keep = jnp.ones((block_q, block_kv), bool)
                if causal:
                    keep &= kpos <= qpos
                if window:
                    keep &= kpos > qpos - window
                bias = jnp.where(keep, 0.0, NEG_INF)[None, None]
            m_new, o_new, l_new = _attend_tile(q_blk, k_blk, v_blk, scale, bias)
            m = jnp.maximum(m_prev, m_new)
            a_prev = jnp.exp(m_prev - m)
            a_new = jnp.exp(m_new - m)
            o = o_prev * a_prev.transpose(0, 2, 1)[..., None] \
                + o_new * a_new.transpose(0, 2, 1)[..., None]
            l = l_prev * a_prev + l_new * a_new
            return (m, o, l), None

        m0 = jnp.full((B, H, block_q), NEG_INF, jnp.float32)
        o0 = jnp.zeros((B, block_q, H, v.shape[-1]), jnp.float32)
        l0 = jnp.zeros((B, H, block_q), jnp.float32)
        (m, o, l), _ = jax.lax.scan(kv_step, (m0, o0, l0),
                                    jnp.arange(n_tiles))
        l = jnp.maximum(l, 1e-37)
        outs.append((o / l.transpose(0, 2, 1)[..., None]).astype(q.dtype))
    return jnp.concatenate(outs, axis=1)


def decode_attention(q, k_cache, v_cache, pos, *, window: int = 0,
                     seq_shard: bool = False):
    """Single-token attention against a (possibly ring-buffered) KV cache.

    q: (B, 1, H, D); caches: (B, Hkv, S_cache, D); pos: scalar count of
    tokens already written (the new token's kv must already be in the
    cache).  For windowed layers the cache is a ring buffer of length
    ``window`` and every slot < min(pos+1, window) is valid.
    ``seq_shard`` pins the score layout to the cache's length sharding
    (flash-decode partials; see shard_decode_scores).
    """
    B, _, H, D = q.shape
    Hkv, S = k_cache.shape[1], k_cache.shape[2]
    rep = H // Hkv
    scale = 1.0 / math.sqrt(D)
    # grouped GQA einsum: contract directly against the Hkv-cache instead
    # of materializing a rep×-replicated copy (the cache is the dominant
    # HBM traffic at long context — §Perf iteration 2)
    qg = q.reshape(B, 1, Hkv, rep, D)
    s = jnp.einsum("bqhrd,bhkd->bhrqk", qg, k_cache,
                   preferred_element_type=jnp.float32) * scale
    s = s.reshape(B, H, 1, S)
    if seq_shard:
        s = shard_decode_scores(s)
    idx = jnp.arange(S)[None, None, None, :]
    valid = idx <= pos if not window else idx < jnp.minimum(pos + 1, S)
    s = jnp.where(valid, s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    if seq_shard:
        p = shard_decode_scores(p)
    pg = p.reshape(B, Hkv, rep, 1, S)
    out = jnp.einsum("bhrqk,bhkd->bqhrd", pg.astype(v_cache.dtype), v_cache)
    return out.reshape(B, 1, H, D).astype(q.dtype)


# ----------------------------------------------------------------------- #
# loss
# ----------------------------------------------------------------------- #
def cross_entropy_loss(hidden, head_w, labels, *, chunk: int = 0,
                       softcap: float = 0.0):
    """Mean next-token cross entropy.

    hidden: (B, S, d); head_w: (d, V); labels: (B, S) with -100 = ignore.
    ``chunk`` > 0 streams the sequence dimension through the vocab matmul
    so only (B, chunk, V) logits are live at once (the TPU-target plan
    for 128k–262k vocabularies).
    """
    B, S, d = hidden.shape

    def piece_loss(h, y):
        logits = (h @ head_w).astype(jnp.float32)
        if softcap:
            logits = jnp.tanh(logits / softcap) * softcap
        lse = jax.nn.logsumexp(logits, axis=-1)
        picked = jnp.take_along_axis(
            logits, jnp.maximum(y, 0)[..., None], axis=-1)[..., 0]
        keep = (y >= 0).astype(jnp.float32)
        return jnp.sum((lse - picked) * keep), jnp.sum(keep)

    if chunk and S > chunk and S % chunk == 0:
        n_chunks = S // chunk
        if n_chunks <= 16:
            # unrolled so HLO cost analysis counts every chunk (a scan
            # body is counted once — see launch/hlo_analysis.py)
            tot, cnt = 0.0, 0.0
            for i in range(n_chunks):
                l, c = piece_loss(hidden[:, i * chunk:(i + 1) * chunk],
                                  labels[:, i * chunk:(i + 1) * chunk])
                tot, cnt = tot + l, cnt + c
        else:
            h_c = hidden.reshape(B, n_chunks, chunk, d).swapaxes(0, 1)
            y_c = labels.reshape(B, n_chunks, chunk).swapaxes(0, 1)

            def step(acc, xy):
                loss, count = piece_loss(*xy)
                return (acc[0] + loss, acc[1] + count), None

            (tot, cnt), _ = jax.lax.scan(step, (0.0, 0.0), (h_c, y_c))
    else:
        tot, cnt = piece_loss(hidden, labels)
    return tot / jnp.maximum(cnt, 1.0)
