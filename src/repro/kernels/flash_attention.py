"""Flash attention forward Pallas TPU kernel (prefill/train path).

Tiling: grid = (batch, q_heads, Sq/block_q, Sk/block_kv) with the KV
dimension innermost and *arbitrary* (sequential) semantics so the online
softmax state for one query tile lives in VMEM scratch across KV steps.
Query/key/value tiles stream HBM→VMEM through BlockSpecs; GQA is handled
by index-mapping each query head onto its KV head, so KV tiles are
fetched once per group instead of being materialized H/Hkv times.
Causal/window masking *skips whole tiles* via ``pl.when`` (work, not just
values, is saved — this matches repro.models.common.blocked_attention,
the jnp oracle).

MXU alignment: block_q/block_kv default to 512/512 and D is expected to
be a multiple of 128 (pad otherwise); accumulation is fp32.

VMEM budget per core (defaults, D=128, bf16):
  q (512×128×2B) + k,v (2×512×128×2B) + o/m/l scratch fp32 ≈ 0.7 MiB.
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


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr, *,
                  causal: bool, window: int, block_q: int, block_kv: int,
                  scale: float, kv_tiles: int):
    qi = pl.program_id(2)
    ki = pl.program_id(3)

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q_lo = qi * block_q
    k_lo = ki * block_kv
    # tile-level visibility test (static shape, dynamic predicate)
    visible = jnp.bool_(True)
    if causal:
        visible &= k_lo <= q_lo + block_q - 1
    if window:
        visible &= k_lo + block_kv - 1 > q_lo - window

    @pl.when(visible)
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32)            # (bq, D)
        k = k_ref[0, 0].astype(jnp.float32)            # (bk, D)
        v = v_ref[0, 0].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        if causal or window:
            qpos = q_lo + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
            kpos = k_lo + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
            keep = jnp.ones(s.shape, jnp.bool_)
            if causal:
                keep &= kpos <= qpos
            if window:
                keep &= kpos > qpos - window
            s = jnp.where(keep, s, NEG_INF)
        m_prev = m_scr[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1))
        p = jnp.exp(s - m_new[:, None])
        alpha = jnp.exp(m_prev - m_new)
        l_scr[...] = l_scr[...] * alpha + jnp.sum(p, axis=1)
        acc_scr[...] = acc_scr[...] * alpha[:, None] \
            + jax.lax.dot_general(p, v, (((1,), (0,)), ((), ())),
                                  preferred_element_type=jnp.float32)
        m_scr[...] = m_new

    @pl.when(ki == kv_tiles - 1)
    def _finalize():
        l = jnp.maximum(l_scr[...], 1e-30)
        o_ref[0, 0] = (acc_scr[...] / l[:, None]).astype(o_ref.dtype)


def flash_attention(q, k, v, *, causal: bool = True, window: int = 0,
                    block_q: int = 512, block_kv: int = 512,
                    interpret: bool = False):
    """q: (B, Sq, H, D); k/v: (B, Sk, Hkv, D) → (B, Sq, H, D)."""
    B, Sq, H, D = q.shape
    Sk, Hkv = k.shape[1], k.shape[2]
    assert H % Hkv == 0
    rep = H // Hkv
    block_q = min(block_q, Sq)
    block_kv = min(block_kv, Sk)
    assert Sq % block_q == 0 and Sk % block_kv == 0
    q_tiles, kv_tiles = Sq // block_q, Sk // block_kv
    scale = 1.0 / math.sqrt(D)

    # (B, S, H, D) → (B, H, S, D) head-major layout for clean tiling
    qt = q.transpose(0, 2, 1, 3)
    kt = k.transpose(0, 2, 1, 3)
    vt = v.transpose(0, 2, 1, 3)

    kernel = functools.partial(
        _flash_kernel, causal=causal, window=window, block_q=block_q,
        block_kv=block_kv, scale=scale, kv_tiles=kv_tiles)

    out = pl.pallas_call(
        kernel,
        grid=(B, H, q_tiles, kv_tiles),
        in_specs=[
            pl.BlockSpec((1, 1, block_q, D),
                         lambda b, h, qi, ki: (b, h, qi, 0)),
            pl.BlockSpec((1, 1, block_kv, D),
                         lambda b, h, qi, ki: (b, h // rep, ki, 0)),
            pl.BlockSpec((1, 1, block_kv, D),
                         lambda b, h, qi, ki: (b, h // rep, ki, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, block_q, D),
                               lambda b, h, qi, ki: (b, h, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((B, H, Sq, D), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q,), jnp.float32),
            pltpu.VMEM((block_q,), jnp.float32),
            pltpu.VMEM((block_q, D), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        interpret=interpret,
    )(qt, kt, vt)
    return out.transpose(0, 2, 1, 3)
