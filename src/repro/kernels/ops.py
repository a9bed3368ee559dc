"""Jitted public wrappers for the Pallas kernels.

Each op compiles its kernel for the TPU, or runs it in interpret mode on
the CPU backend (where the tests validate the kernel bodies against the
jnp oracles in ref.py); any other backend is an error, so no run can
fall back to the interpreter without saying so.  The ops pad ragged
shapes to tile multiples and expose the signatures the model code uses.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from .decode_attention import decode_attention as _decode_attention
from .flash_attention import flash_attention as _flash_attention
from .rglru_scan import rglru_scan as _rglru_scan
from .ssd_scan import ssd_scan as _ssd_scan


def _interpret() -> bool:
    """True on the CPU backend (the test platform), False on the TPU."""
    backend = jax.default_backend()
    if backend not in ("tpu", "cpu"):
        raise RuntimeError(
            f"Pallas kernels target the TPU (or the CPU interpreter in "
            f"tests); the {backend!r} backend has neither")
    return backend == "cpu"


def _pad_to(x, multiple: int, axis: int):
    n = x.shape[axis]
    pad = (-n) % multiple
    if pad == 0:
        return x, n
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths), n


@functools.partial(jax.jit, static_argnames=("causal", "window", "block_q",
                                             "block_kv"))
def flash_attention(q, k, v, *, causal: bool = True, window: int = 0,
                    block_q: int = 512, block_kv: int = 512):
    """Flash attention with automatic sequence padding.

    Padded KV positions are masked by causality (query padding rows are
    discarded); for non-causal use the kernel requires aligned shapes.
    """
    B, Sq, H, D = q.shape
    bq = min(block_q, max(16, 1 << (Sq - 1).bit_length() if Sq < block_q else block_q))
    bkv = min(block_kv, max(16, 1 << (k.shape[1] - 1).bit_length()
                            if k.shape[1] < block_kv else block_kv))
    qp, sq = _pad_to(q, bq, 1)
    kp, sk = _pad_to(k, bkv, 1)
    vp, _ = _pad_to(v, bkv, 1)
    if not causal and (qp.shape[1] != Sq or kp.shape[1] != k.shape[1]):
        raise ValueError("non-causal flash_attention requires aligned shapes")
    out = _flash_attention(qp, kp, vp, causal=causal, window=window,
                           block_q=bq, block_kv=bkv, interpret=_interpret())
    return out[:, :sq]


@functools.partial(jax.jit, static_argnames=("block_kv",))
def decode_attention(q, k_cache, v_cache, lengths, *, block_kv: int = 512):
    """Flash-decode against a head-major (B, Hkv, S, D) KV cache with
    per-batch valid lengths."""
    S = k_cache.shape[2]
    bkv = min(block_kv, S)
    kp, _ = _pad_to(k_cache, bkv, 2)
    vp, _ = _pad_to(v_cache, bkv, 2)
    return _decode_attention(q, kp, vp, lengths, block_kv=bkv,
                             interpret=_interpret())


@functools.partial(jax.jit, static_argnames=("chunk",))
def ssd_scan(x, dt, a_log, B_in, C_in, *, chunk: int = 64):
    """Mamba2 SSD over (B, S, H, P) inputs; S must be a chunk multiple."""
    return _ssd_scan(x, dt, a_log, B_in, C_in, chunk=chunk,
                     interpret=_interpret())


@functools.partial(jax.jit, static_argnames=("block_s", "block_w"))
def rglru_scan(a, b, *, block_s: int = 128, block_w: int = 512):
    """RG-LRU linear recurrence h_t = a_t·h_{t-1} + b_t over (B, S, W)."""
    B, S, W = a.shape
    bs = min(block_s, S)
    bw = min(block_w, W)
    while S % bs:
        bs //= 2
    while W % bw:
        bw //= 2
    return _rglru_scan(a, b, block_s=max(1, bs), block_w=max(1, bw),
                       interpret=_interpret())
