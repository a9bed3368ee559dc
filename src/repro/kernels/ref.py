"""Pure-jnp oracles for every Pallas kernel.

These are the ground-truth implementations the kernel tests
``assert_allclose`` against, shared with the model code so the kernels
and the models can never drift apart.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from ..models.common import naive_attention
from ..models.rglru import rglru_scan as _rglru_scan_params
from ..models.ssm import ssd_chunked as _ssd_chunked


def flash_attention_ref(q, k, v, *, causal: bool = True, window: int = 0):
    """Oracle for kernels.flash_attention. q/k/v: (B, S, H, D)."""
    return naive_attention(q, k, v, causal=causal, window=window)


def decode_attention_ref(q, k_cache, v_cache, lengths):
    """Oracle for kernels.decode_attention.

    q: (B, 1, H, D); caches: (B, Hkv, S, D); lengths: (B,) valid kv counts.
    """
    B, _, H, D = q.shape
    Hkv, S = k_cache.shape[1], k_cache.shape[2]
    rep = H // Hkv
    k = jnp.repeat(k_cache, rep, axis=1)
    v = jnp.repeat(v_cache, rep, axis=1)
    s = jnp.einsum("bqhd,bhkd->bhqk", q, k,
                   preferred_element_type=jnp.float32) / math.sqrt(D)
    valid = jnp.arange(S)[None, None, None, :] < lengths[:, None, None, None]
    s = jnp.where(valid, s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhqk,bhkd->bqhd", p.astype(v.dtype), v).astype(q.dtype)


def ssd_scan_ref(x, dt, a_log, B_in, C_in, *, chunk: int = 64):
    """Oracle for kernels.ssd_scan (sequential recurrence, not chunked)."""
    Bb, S, H, P = x.shape
    G, N = B_in.shape[2], B_in.shape[3]
    A = -jnp.exp(a_log.astype(jnp.float32))
    Bh = jnp.repeat(B_in, H // G, axis=2)     # (B,S,H,N)
    Ch = jnp.repeat(C_in, H // G, axis=2)

    def step(h, inp):
        x_t, dt_t, b_t, c_t = inp
        da = jnp.exp(dt_t * A)                # (B,H)
        h = h * da[..., None, None] + (dt_t[..., None, None]
                                       * x_t[..., None] * b_t[:, :, None, :])
        y = jnp.einsum("bhpn,bhn->bhp", h, c_t)
        return h, y

    h0 = jnp.zeros((Bb, H, P, N), jnp.float32)
    xs = (x.swapaxes(0, 1), dt.swapaxes(0, 1),
          Bh.swapaxes(0, 1), Ch.swapaxes(0, 1))
    h, ys = jax.lax.scan(step, h0, xs)
    return ys.swapaxes(0, 1).astype(x.dtype), h


def ssd_chunked_ref(x, dt, a_log, B_in, C_in, *, chunk: int = 64):
    """The model's chunked SSD (itself validated against ssd_scan_ref)."""
    return _ssd_chunked(x, dt, a_log, B_in, C_in, chunk=chunk)


def rglru_scan_ref(a, b, *, init_h=None):
    """Oracle for kernels.rglru_scan: h_t = a_t·h_{t-1} + b_t, sequential.

    a/b: (B, S, W) fp32 → (h_all (B,S,W), h_final (B,W)).
    """
    def step(h, ab):
        a_t, b_t = ab
        h = a_t * h + b_t
        return h, h

    B, S, W = a.shape
    h0 = jnp.zeros((B, W), jnp.float32) if init_h is None else init_h
    h_final, hs = jax.lax.scan(step, h0, (a.swapaxes(0, 1), b.swapaxes(0, 1)))
    return hs.swapaxes(0, 1), h_final
