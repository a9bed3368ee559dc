"""Mamba2 SSD chunked-scan Pallas TPU kernel.

Tiling: grid = (batch, heads, S/chunk) with the chunk dimension
sequential; the (P, N) recurrent state for one (b, h) pair lives in VMEM
scratch across chunk steps.  Each grid step does the intra-chunk
quadratic block (two (Q×Q)·(Q×P) matmuls — MXU work) plus the O(P·N)
state update, which is exactly the SSD decomposition of
repro.models.ssm.ssd_chunked (the jnp oracle derives from the same
math; tests assert both against the sequential-recurrence reference).

Chunk length Q defaults to 64 (trades VMEM for MXU utilization:
Q=64, P=64, N=128 keeps all tiles inside one MXU pass); state scratch is
P×N fp32 = 32 KiB.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _ssd_kernel(x_ref, dt_ref, a_ref, b_ref, c_ref, y_ref, state_scr, *,
                chunk: int):
    ci = pl.program_id(2)

    @pl.when(ci == 0)
    def _init():
        state_scr[...] = jnp.zeros_like(state_scr)

    x = x_ref[0, 0].astype(jnp.float32)          # (Q, P)
    dt = dt_ref[0, 0].astype(jnp.float32)        # (Q,)
    A = -jnp.exp(a_ref[0].astype(jnp.float32))   # scalar
    Bm = b_ref[0, 0].astype(jnp.float32)         # (Q, N)
    Cm = c_ref[0, 0].astype(jnp.float32)         # (Q, N)

    dA = dt * A                                  # (Q,) ≤ 0
    cs = jnp.cumsum(dA)                          # inclusive
    # intra-chunk: y_i += Σ_{j<=i} C_i·B_j exp(cs_i - cs_j) dt_j x_j
    scores = jax.lax.dot_general(Cm, Bm, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
    decay = cs[:, None] - cs[None, :]
    Q = chunk
    ii = jax.lax.broadcasted_iota(jnp.int32, (Q, Q), 0)
    jj = jax.lax.broadcasted_iota(jnp.int32, (Q, Q), 1)
    L = jnp.where(jj <= ii, jnp.exp(decay), 0.0)
    dtx = x * dt[:, None]                        # (Q, P)
    y = jax.lax.dot_general(scores * L, dtx, (((1,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32)

    # inter-chunk: y_i += C_i · h_prev · exp(cs_i)
    h_prev = state_scr[...]                      # (P, N)
    y += jax.lax.dot_general(Cm, h_prev, (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32) \
        * jnp.exp(cs)[:, None]

    # state update: h = exp(cs_end)·h_prev + Σ_j exp(cs_end - cs_j) dt_j x_j ⊗ B_j
    seg = jnp.exp(cs[-1] - cs) * dt              # (Q,)
    new_state = jax.lax.dot_general(
        dtx * (seg / jnp.maximum(dt, 1e-20))[:, None], Bm,
        (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)      # (P, N)
    state_scr[...] = jnp.exp(cs[-1]) * h_prev + new_state
    y_ref[0, 0] = y.astype(y_ref.dtype)


def ssd_scan(x, dt, a_log, B_in, C_in, *, chunk: int = 64,
             interpret: bool = False):
    """x: (B, S, H, P); dt: (B, S, H); a_log: (H,); B_in/C_in: (B, S, G, N).

    Returns y (B, S, H, P).  Groups are expanded to heads before the call
    (G→H) to keep BlockSpecs rank-uniform.
    """
    Bb, S, H, P = x.shape
    G, N = B_in.shape[2], B_in.shape[3]
    assert S % chunk == 0, (S, chunk)
    nc = S // chunk
    rep = H // G
    Bh = jnp.repeat(B_in, rep, axis=2)           # (B, S, H, N)
    Ch = jnp.repeat(C_in, rep, axis=2)

    # head-major layouts: (B, H, S, ·)
    xt = x.transpose(0, 2, 1, 3)
    dtt = dt.transpose(0, 2, 1)
    Bt = Bh.transpose(0, 2, 1, 3)
    Ct = Ch.transpose(0, 2, 1, 3)

    out = pl.pallas_call(
        functools.partial(_ssd_kernel, chunk=chunk),
        grid=(Bb, H, nc),
        in_specs=[
            pl.BlockSpec((1, 1, chunk, P), lambda b, h, c: (b, h, c, 0)),
            pl.BlockSpec((1, 1, chunk), lambda b, h, c: (b, h, c)),
            pl.BlockSpec((1,), lambda b, h, c: (h,), memory_space=pltpu.SMEM),
            pl.BlockSpec((1, 1, chunk, N), lambda b, h, c: (b, h, c, 0)),
            pl.BlockSpec((1, 1, chunk, N), lambda b, h, c: (b, h, c, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, chunk, P), lambda b, h, c: (b, h, c, 0)),
        out_shape=jax.ShapeDtypeStruct((Bb, H, S, P), x.dtype),
        scratch_shapes=[pltpu.VMEM((P, N), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(xt, dtt, a_log, Bt, Ct)
    return out.transpose(0, 2, 1, 3)
