"""Flash-decode Pallas TPU kernel: one query token vs a long KV cache.

Layout: the cache is head-major, ``(B, Hkv, S, D)``, so every KV tile
is a plain 2-D ``(block_kv, D)`` slab — no kv-head axis in the sublane
dimension, which Mosaic would pad to a full tile (16× for Hkv=1 in
bf16).  The ``rep = H // Hkv`` query heads of one GQA group arrive as a
``(rep, D)`` tile, so each KV tile is read once per group.

Tiling: grid = (batch, kv_heads, S/block_kv) with the KV dimension
sequential; the online-softmax state lives in 2-D VMEM scratch.  The
per-sequence valid lengths are scalar-prefetched into SMEM: they mask
ring/partially-filled caches, skip the compute of tiles past the end,
and clamp the KV index map so those tiles are never fetched.

This kernel is the TPU analogue of the paper's "intra-op parallelism"
for decode: the KV cache's *length* dimension is what a thin instance
shards across its chips (DESIGN.md §5), and within one chip this kernel
tiles the same axis through VMEM.

VMEM per step (gemma3-1b: block_kv=1024, D=256, bf16): k,v tiles
double-buffered 4×1024×256×2B = 2 MiB, plus the (rep, bk) f32 scores
and the (rep, D) f32 accumulator.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -0.7 * float(np.finfo(np.float32).max)


def _decode_kernel(len_ref, q_ref, k_ref, v_ref, o_ref, m_scr, l_scr,
                   acc_scr, *, block_kv: int, scale: float):
    b, ki = pl.program_id(0), pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    length = len_ref[b]
    k_lo = ki * block_kv

    @pl.when(k_lo < length)
    def _compute():
        s = jax.lax.dot_general(                       # (rep, bk)
            q_ref[...], k_ref[...], (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale
        kpos = k_lo + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        s = jnp.where(kpos < length, s, NEG_INF)
        m_prev = m_scr[...]                            # (rep, 1)
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_scr[...] = alpha * l_scr[...] + jnp.sum(p, axis=1, keepdims=True)
        pv = jax.lax.dot_general(                      # (rep, D)
            p.astype(v_ref.dtype), v_ref[...], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        acc_scr[...] = alpha * acc_scr[...] + pv
        m_scr[...] = m_new

    @pl.when(ki == pl.num_programs(2) - 1)
    def _finalize():
        l = jnp.maximum(l_scr[...], 1e-30)
        o_ref[...] = (acc_scr[...] / l).astype(o_ref.dtype)


def _validate(q, k_cache, v_cache, lengths, block_kv: int) -> None:
    """Shape/dtype checks with actionable errors (a bad call otherwise
    surfaces as an opaque Pallas lowering failure deep in the grid)."""
    if q.ndim != 4 or q.shape[1] != 1:
        raise ValueError(
            f"decode_attention: q must be (B, 1, H, D), got {q.shape}")
    if k_cache.ndim != 4 or v_cache.ndim != 4:
        raise ValueError(
            "decode_attention: caches must be (B, Hkv, S, D), got "
            f"k={k_cache.shape} v={v_cache.shape}")
    if k_cache.shape != v_cache.shape:
        raise ValueError(
            f"decode_attention: k/v cache shapes differ: "
            f"{k_cache.shape} vs {v_cache.shape}")
    B, _, H, D = q.shape
    Bk, Hkv, S, Dk = k_cache.shape
    if Bk != B:
        raise ValueError(
            f"decode_attention: batch mismatch: q has B={B}, cache has "
            f"B={Bk}")
    if Dk != D:
        raise ValueError(
            f"decode_attention: head dim mismatch: q has D={D}, cache has "
            f"D={Dk}")
    if Hkv > H or H % Hkv != 0:
        raise ValueError(
            f"decode_attention: q heads H={H} must be a multiple of cache "
            f"kv heads Hkv={Hkv} (GQA groups)")
    if q.dtype != k_cache.dtype:
        raise ValueError(
            f"decode_attention: dtype mismatch: q is {q.dtype}, cache is "
            f"{k_cache.dtype}")
    bkv = min(block_kv, S)
    if S % bkv != 0:
        raise ValueError(
            f"decode_attention: cache length S={S} must be a multiple of "
            f"block_kv={bkv}; pad the cache (ops.decode_attention does "
            "this automatically)")
    if lengths.shape != (B,):
        raise ValueError(
            f"decode_attention: lengths must be ({B},), got {lengths.shape}")


def decode_attention(q, k_cache, v_cache, lengths, *, block_kv: int = 512,
                     interpret: bool = False):
    """q: (B, 1, H, D); caches: (B, Hkv, S, D); lengths: (B,) int32.

    Returns (B, 1, H, D).  Cache positions >= lengths[b] are masked.
    """
    _validate(q, k_cache, v_cache, lengths, block_kv)
    B, _, H, D = q.shape
    Hkv, S = k_cache.shape[1], k_cache.shape[2]
    rep = H // Hkv
    block_kv = min(block_kv, S)
    kv_tiles = S // block_kv

    def q_map(b, h, ki, lens):
        return b, h, 0, 0

    def kv_map(b, h, ki, lens):
        # tiles wholly past the valid length repeat the last needed block
        # index, so the pipeline skips their DMA
        last = jnp.maximum(lens[b] - 1, 0) // block_kv
        return b, h, jnp.minimum(ki, last), 0

    kernel = functools.partial(_decode_kernel, block_kv=block_kv,
                               scale=1.0 / math.sqrt(D))
    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(B, Hkv, kv_tiles),
            in_specs=[
                pl.BlockSpec((None, None, rep, D), q_map),
                pl.BlockSpec((None, None, block_kv, D), kv_map),
                pl.BlockSpec((None, None, block_kv, D), kv_map),
            ],
            out_specs=pl.BlockSpec((None, None, rep, D), q_map),
            scratch_shapes=[
                pltpu.VMEM((rep, 1), jnp.float32),
                pltpu.VMEM((rep, 1), jnp.float32),
                pltpu.VMEM((rep, D), jnp.float32),
            ]),
        out_shape=jax.ShapeDtypeStruct((B, Hkv, rep, D), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(lengths.astype(jnp.int32), q.reshape(B, Hkv, rep, D), k_cache,
      v_cache)
    return out.reshape(B, 1, H, D)
