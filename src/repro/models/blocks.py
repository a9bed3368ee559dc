"""Per-kind layer blocks and the block dispatcher.

Kinds (configs.base): ATTN (global causal), LOCAL_ATTN (sliding window),
ENC (bidirectional), DEC (causal + cross-attention), MLA / MLA_MOE
(DeepSeek multi-head latent attention with dense or MoE FFN), RGLRU
(Griffin recurrent), SSM (Mamba2 SSD).

Every block follows the same functional contract:

    params            = init_block(rng, cfg, kind)
    cache             = init_block_cache(cfg, kind, batch, max_len)
    x', cache'        = apply_block(params, x, cfg, kind, mode=..., ...)

``mode`` ∈ {"train", "prefill", "decode"}; decode consumes/produces the
cache and processes exactly one token.

A scanned decode step hands each block the stacked cache of every
repeat (leaves ``(R, ...)``) and the repeat's index ``layer``.  The block
reads its layer's slice, with this step's position written into the
slice, and returns only what the step writes: the new position of each
K/V or latent cache, or a recurrent layer's whole new state.  After the
scan :func:`write_decode_step` writes every layer's entries into the
stack at once, in place (the stacked cache is never copied or rebuilt);
cross-attention memory is read-only and kept.
"""

from __future__ import annotations

import math
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from ..configs.base import (ATTN, DEC, ENC, LOCAL_ATTN, MLA, MLA_MOE, RGLRU,
                            SSM, ModelConfig)
from .common import (apply_mlp, apply_norm, apply_rope, blocked_attention,
                     decode_attention, dense_init, init_mlp, init_norm,
                     rms_norm)
from .moe import apply_moe, init_moe
from .rglru import apply_rglru_block, init_rglru_block, init_rglru_cache
from .ssm import apply_ssm_block, init_ssm_block, init_ssm_cache

_ATTN_FAMILY = (ATTN, LOCAL_ATTN, ENC, DEC)


def _dtype(cfg: ModelConfig):
    return jnp.dtype(cfg.dtype)


def _gated(cfg: ModelConfig) -> bool:
    return cfg.act in ("silu", "gelu")


def _rope_theta(cfg: ModelConfig, kind: str) -> float:
    return cfg.rope_local_theta if kind == LOCAL_ATTN else cfg.rope_theta


# ===================================================================== #
# standard attention family
# ===================================================================== #
def _init_attention(rng, cfg: ModelConfig, dtype) -> Dict:
    d, H, Hkv, Dh = (cfg.d_model, cfg.n_heads, cfg.n_kv_heads,
                     cfg.resolved_head_dim)
    k = jax.random.split(rng, 4)
    p = {
        "wq": dense_init(k[0], (d, H, Dh), dtype=dtype),
        "wk": dense_init(k[1], (d, Hkv, Dh), dtype=dtype),
        "wv": dense_init(k[2], (d, Hkv, Dh), dtype=dtype),
        "wo": dense_init(k[3], (H, Dh, d), in_axis=(0, 1), dtype=dtype),
    }
    if cfg.qkv_bias:
        p["bq"] = jnp.zeros((H, Dh), dtype)
        p["bk"] = jnp.zeros((Hkv, Dh), dtype)
        p["bv"] = jnp.zeros((Hkv, Dh), dtype)
    if cfg.qk_norm:
        p["q_norm"] = {"scale": jnp.zeros((Dh,), jnp.float32)}
        p["k_norm"] = {"scale": jnp.zeros((Dh,), jnp.float32)}
    return p


def _qkv(p, x, cfg: ModelConfig, kind: str, positions):
    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"])
    k = jnp.einsum("bsd,dhk->bshk", x, p["wk"])
    v = jnp.einsum("bsd,dhk->bshk", x, p["wv"])
    if cfg.qkv_bias:
        q, k, v = q + p["bq"], k + p["bk"], v + p["bv"]
    if cfg.qk_norm:
        q = rms_norm(q, p["q_norm"]["scale"], cfg.norm_eps)
        k = rms_norm(k, p["k_norm"]["scale"], cfg.norm_eps)
    theta = _rope_theta(cfg, kind)
    q = apply_rope(q, positions, theta, cfg.rope_pct)
    k = apply_rope(k, positions, theta, cfg.rope_pct)
    return q, k, v


def init_attn_block(rng, cfg: ModelConfig, kind: str) -> Dict:
    dtype = _dtype(cfg)
    ks = jax.random.split(rng, 6)
    p = {
        "pre_attn": init_norm(ks[0], cfg.d_model, cfg.norm),
        "attn": _init_attention(ks[1], cfg, dtype),
        "pre_mlp": init_norm(ks[2], cfg.d_model, cfg.norm),
        "mlp": init_mlp(ks[3], cfg.d_model, cfg.d_ff, gated=_gated(cfg),
                        dtype=dtype),
    }
    if cfg.post_norms:
        p["post_attn"] = init_norm(ks[4], cfg.d_model, cfg.norm)
        p["post_mlp"] = init_norm(ks[5], cfg.d_model, cfg.norm)
    if kind == DEC:
        p["pre_cross"] = init_norm(ks[4], cfg.d_model, cfg.norm)
        p["cross"] = _init_attention(ks[5], cfg, dtype)
    return p


def _attn_cache_len(cfg: ModelConfig, kind: str, max_len: int) -> int:
    if kind == LOCAL_ATTN and cfg.sliding_window:
        return min(max_len, cfg.sliding_window)
    return max_len


def init_attn_cache(cfg: ModelConfig, kind: str, batch: int, max_len: int,
                    memory_len: int = 0) -> Dict:
    """Self-attention k/v are head-major, (B, Hkv, L, Dh): each kv head's
    sequence is one contiguous (L, Dh) slab, the layout the decode kernel
    tiles.  Cross-attention memory stays (B, M, Hkv, Dh)."""
    dtype = _dtype(cfg)
    Hkv, Dh = cfg.n_kv_heads, cfg.resolved_head_dim
    L = _attn_cache_len(cfg, kind, max_len)
    cache = {
        "k": jnp.zeros((batch, Hkv, L, Dh), dtype),
        "v": jnp.zeros((batch, Hkv, L, Dh), dtype),
    }
    if kind == DEC:
        cache["cross_k"] = jnp.zeros((batch, memory_len, Hkv, Dh), dtype)
        cache["cross_v"] = jnp.zeros((batch, memory_len, Hkv, Dh), dtype)
    return cache


_KV_SEQ_AXIS = 2    # sequence axis of the head-major (B, Hkv, L, Dh) k/v


def _write_full_cache(cache_arr, new, pos, axis: int = 1):
    """Write a slab at sequence offset pos along ``axis``."""
    return jax.lax.dynamic_update_slice_in_dim(cache_arr, new.astype(
        cache_arr.dtype), pos, axis=axis)


def _ring_slot(pos, window):
    """Slot of token pos in a ring cache of `window` slots."""
    return jnp.asarray(pos) % window


def _write_ring(cache_arr, new, pos, window):
    """Write one head-major token at slot pos % window (decode)."""
    return _write_full_cache(cache_arr, new, _ring_slot(pos, window),
                             _KV_SEQ_AXIS)


def _layer_slice(cache_arr, layer):
    """A stacked cache leaf's slice at ``layer`` (the leaf if None)."""
    if layer is None:
        return cache_arr
    return jax.lax.dynamic_index_in_dim(cache_arr, layer, keepdims=False)


def _prefill_ring(cache_arr, k_seq, window):
    """Store the last `window` tokens of a head-major (B, Hkv, S, Dh)
    sequence so that token p sits in slot p%window."""
    S = k_seq.shape[_KV_SEQ_AXIS]
    if S <= window:
        return _write_full_cache(cache_arr, k_seq, 0, _KV_SEQ_AXIS)
    tail = k_seq[:, :, -window:]
    return jnp.roll(tail.astype(cache_arr.dtype), shift=S % window,
                    axis=_KV_SEQ_AXIS)


def apply_attn_block(params, x, cfg: ModelConfig, kind: str, *, mode: str,
                     positions=None, pos=None, cache: Optional[Dict] = None,
                     memory=None, layer=None):
    """x: (B, S, d). decode: S == 1 and `pos` is the scalar write position;
    with `layer` (scanned decode) the cache is stacked and the block
    returns this step's k/v instead of the cache."""
    causal = kind != ENC
    window = cfg.sliding_window if kind == LOCAL_ATTN else 0
    res = x
    h = apply_norm(params["pre_attn"], x, cfg.norm, cfg.norm_eps)

    if mode == "decode":
        assert cache is not None and pos is not None
        q, k, v = _qkv(params["attn"], h, cfg, kind,
                       jnp.full((1,), pos, jnp.int32)[None, :])
        k, v = k.swapaxes(1, 2), v.swapaxes(1, 2)      # (B, Hkv, 1, Dh)
        ck = _layer_slice(cache["k"], layer)
        cv = _layer_slice(cache["v"], layer)
        if window:
            ck = _write_ring(ck, k, pos, window)
            cv = _write_ring(cv, v, pos, window)
        else:
            ck = _write_full_cache(ck, k, pos, _KV_SEQ_AXIS)
            cv = _write_full_cache(cv, v, pos, _KV_SEQ_AXIS)
        if cfg.use_pallas_kernels:
            # Pallas flash-decode: position mask → per-batch valid length.
            # Full cache: slots 0..pos hold tokens 0..pos.  Ring cache
            # (window): the last min(pos+1, L) tokens occupy some
            # permutation of the first min(pos+1, L) slots — softmax is
            # permutation-invariant over KV, so a plain length mask is
            # exact for both layouts.
            from ..kernels import ops as kernel_ops
            L = ck.shape[_KV_SEQ_AXIS]
            lengths = jnp.broadcast_to(
                jnp.minimum(jnp.asarray(pos, jnp.int32) + 1, L),
                (q.shape[0],))
            attn = kernel_ops.decode_attention(
                q.astype(ck.dtype), ck, cv, lengths,
                block_kv=cfg.attn_block_kv)
        else:
            attn = decode_attention(
                q, ck, cv, pos, window=window,
                seq_shard=cfg.decode_seq_shard and not window)
        new_cache = (dict(k=k, v=v) if layer is not None
                     else dict(cache, k=ck, v=cv))
    else:
        q, k, v = _qkv(params["attn"], h, cfg, kind, positions)
        if cfg.seq_sharding and cfg.sp_gather_heads:
            from .common import shard_heads
            q, k, v = shard_heads(q), shard_heads(k), shard_heads(v)
        if cfg.use_pallas_kernels and causal:
            from ..kernels import ops as kernel_ops
            attn = kernel_ops.flash_attention(
                q.astype(v.dtype), k.astype(v.dtype), v, causal=True,
                window=window, block_q=cfg.attn_block_q,
                block_kv=cfg.attn_block_kv)
        else:
            attn = blocked_attention(q, k, v, causal=causal, window=window,
                                     block_q=cfg.attn_block_q,
                                     block_kv=cfg.attn_block_kv)
        if mode == "prefill":
            assert cache is not None
            k, v = k.swapaxes(1, 2), v.swapaxes(1, 2)  # (B, Hkv, S, Dh)
            if window:
                ck = _prefill_ring(cache["k"], k, window)
                cv = _prefill_ring(cache["v"], v, window)
            else:
                ck = _write_full_cache(cache["k"], k, 0, _KV_SEQ_AXIS)
                cv = _write_full_cache(cache["v"], v, 0, _KV_SEQ_AXIS)
            cache = dict(cache, k=ck, v=cv)

    out = jnp.einsum("bshk,hkd->bsd", attn, params["attn"]["wo"])
    if cfg.post_norms:
        out = apply_norm(params["post_attn"], out, cfg.norm, cfg.norm_eps)
    x = res + out

    if kind == DEC:
        assert memory is not None or (cache is not None and mode == "decode")
        res = x
        h = apply_norm(params["pre_cross"], x, cfg.norm, cfg.norm_eps)
        cp = params["cross"]
        q = jnp.einsum("bsd,dhk->bshk", h, cp["wq"])
        if mode == "decode":
            mk = _layer_slice(cache["cross_k"], layer)
            mv = _layer_slice(cache["cross_v"], layer)
        else:
            mk = jnp.einsum("bsd,dhk->bshk", memory, cp["wk"])
            mv = jnp.einsum("bsd,dhk->bshk", memory, cp["wv"])
            if mode == "prefill":
                cache = dict(cache, cross_k=mk.astype(cache["cross_k"].dtype),
                             cross_v=mv.astype(cache["cross_v"].dtype))
        attn = blocked_attention(q, mk, mv, causal=False,
                                 block_q=cfg.attn_block_q,
                                 block_kv=cfg.attn_block_kv)
        x = res + jnp.einsum("bshk,hkd->bsd", attn, cp["wo"])

    res = x
    h = apply_norm(params["pre_mlp"], x, cfg.norm, cfg.norm_eps)
    out = apply_mlp(params["mlp"], h, cfg.act, gated=_gated(cfg))
    if cfg.post_norms:
        out = apply_norm(params["post_mlp"], out, cfg.norm, cfg.norm_eps)
    if mode == "decode":
        cache = new_cache
    return res + out, cache


# ===================================================================== #
# multi-head latent attention (DeepSeek V2/V3)
# ===================================================================== #
def init_mla_block(rng, cfg: ModelConfig, kind: str, dense_layer: bool
                   ) -> Dict:
    dtype = _dtype(cfg)
    mla = cfg.mla
    assert mla is not None
    d, H = cfg.d_model, cfg.n_heads
    qk_dim = mla.qk_nope_head_dim + mla.qk_rope_head_dim
    ks = jax.random.split(rng, 10)
    p: Dict = {
        "pre_attn": init_norm(ks[0], d, cfg.norm),
        "pre_mlp": init_norm(ks[1], d, cfg.norm),
        "wkv_a": dense_init(ks[2], (d, mla.kv_lora_rank + mla.qk_rope_head_dim),
                            dtype=dtype),
        "kv_norm": {"scale": jnp.zeros((mla.kv_lora_rank,), jnp.float32)},
        "wk_b": dense_init(ks[3], (mla.kv_lora_rank, H, mla.qk_nope_head_dim),
                           dtype=dtype),
        "wv_b": dense_init(ks[4], (mla.kv_lora_rank, H, mla.v_head_dim),
                           dtype=dtype),
        "wo": dense_init(ks[5], (H, mla.v_head_dim, d), in_axis=(0, 1),
                         dtype=dtype),
    }
    if mla.q_lora_rank:
        p["wq_a"] = dense_init(ks[6], (d, mla.q_lora_rank), dtype=dtype)
        p["q_norm"] = {"scale": jnp.zeros((mla.q_lora_rank,), jnp.float32)}
        p["wq_b"] = dense_init(ks[7], (mla.q_lora_rank, H, qk_dim), dtype=dtype)
    else:
        p["wq"] = dense_init(ks[6], (d, H, qk_dim), dtype=dtype)
    if dense_layer or kind == MLA:
        ff = cfg.dense_ff or cfg.d_ff
        p["mlp"] = init_mlp(ks[8], d, ff, gated=_gated(cfg), dtype=dtype)
    else:
        p["moe"] = init_moe(ks[9], cfg, dtype)
    return p


def init_mla_cache(cfg: ModelConfig, batch: int, max_len: int) -> Dict:
    mla = cfg.mla
    dtype = _dtype(cfg)
    return {
        "c_kv": jnp.zeros((batch, max_len, mla.kv_lora_rank), dtype),
        "k_rope": jnp.zeros((batch, max_len, mla.qk_rope_head_dim), dtype),
    }


def _mla_q(params, h, cfg: ModelConfig, positions):
    mla = cfg.mla
    if mla.q_lora_rank:
        qa = rms_norm(h @ params["wq_a"], params["q_norm"]["scale"],
                      cfg.norm_eps)
        q = jnp.einsum("bsl,lhk->bshk", qa, params["wq_b"])
    else:
        q = jnp.einsum("bsd,dhk->bshk", h, params["wq"])
    q_nope = q[..., :mla.qk_nope_head_dim]
    q_rope = apply_rope(q[..., mla.qk_nope_head_dim:], positions,
                        cfg.rope_theta)
    return q_nope, q_rope


def _mla_kv_latent(params, h, cfg: ModelConfig, positions):
    mla = cfg.mla
    kv = h @ params["wkv_a"]
    c_kv = rms_norm(kv[..., :mla.kv_lora_rank], params["kv_norm"]["scale"],
                    cfg.norm_eps)
    k_rope = apply_rope(kv[..., mla.kv_lora_rank:], positions, cfg.rope_theta)
    return c_kv, k_rope


def apply_mla_block(params, x, cfg: ModelConfig, kind: str, *, mode: str,
                    positions=None, pos=None, cache: Optional[Dict] = None,
                    layer=None):
    mla = cfg.mla
    scale = 1.0 / math.sqrt(mla.qk_nope_head_dim + mla.qk_rope_head_dim)
    res = x
    h = apply_norm(params["pre_attn"], x, cfg.norm, cfg.norm_eps)

    if mode == "decode":
        assert cache is not None and pos is not None
        posv = jnp.full((1,), pos, jnp.int32)[None, :]
        q_nope, q_rope = _mla_q(params, h, cfg, posv)          # (B,1,H,·)
        c_t, kr_t = _mla_kv_latent(params, h, cfg, posv)       # (B,1,·)
        c_kv = _write_full_cache(_layer_slice(cache["c_kv"], layer), c_t, pos)
        k_rope = _write_full_cache(_layer_slice(cache["k_rope"], layer),
                                   kr_t, pos)
        cache = (dict(c_kv=c_t, k_rope=kr_t) if layer is not None
                 else dict(cache, c_kv=c_kv, k_rope=k_rope))
        # absorbed attention: score in latent space, expand after combine
        q_lat = jnp.einsum("bqhn,lhn->bqhl", q_nope, params["wk_b"])
        s = (jnp.einsum("bqhl,bsl->bhqs", q_lat.astype(jnp.float32),
                        c_kv.astype(jnp.float32))
             + jnp.einsum("bqhr,bsr->bhqs", q_rope.astype(jnp.float32),
                          k_rope.astype(jnp.float32))) * scale
        S = c_kv.shape[1]
        valid = jnp.arange(S)[None, None, None, :] <= pos
        s = jnp.where(valid, s, -1e30)
        p_attn = jax.nn.softmax(s, axis=-1)
        o_lat = jnp.einsum("bhqs,bsl->bqhl", p_attn.astype(c_kv.dtype), c_kv)
        attn = jnp.einsum("bqhl,lhv->bqhv", o_lat, params["wv_b"])
    else:
        q_nope, q_rope = _mla_q(params, h, cfg, positions)
        c_kv, k_rope = _mla_kv_latent(params, h, cfg, positions)
        k_nope = jnp.einsum("bsl,lhn->bshn", c_kv, params["wk_b"])
        v = jnp.einsum("bsl,lhv->bshv", c_kv, params["wv_b"])
        H = cfg.n_heads
        k = jnp.concatenate(
            [k_nope, jnp.broadcast_to(k_rope[:, :, None, :],
                                      (*k_nope.shape[:3], k_rope.shape[-1]))],
            axis=-1)
        q = jnp.concatenate([q_nope, q_rope], axis=-1)
        if cfg.seq_sharding and cfg.sp_gather_heads:
            from .common import shard_heads
            q, k, v = shard_heads(q), shard_heads(k), shard_heads(v)
        attn = blocked_attention(q, k, v, causal=True,
                                 block_q=cfg.attn_block_q,
                                 block_kv=cfg.attn_block_kv)
        if mode == "prefill":
            assert cache is not None
            cache = dict(cache,
                         c_kv=_write_full_cache(cache["c_kv"], c_kv, 0),
                         k_rope=_write_full_cache(cache["k_rope"], k_rope, 0))

    x = res + jnp.einsum("bshv,hvd->bsd", attn, params["wo"])
    res = x
    h = apply_norm(params["pre_mlp"], x, cfg.norm, cfg.norm_eps)
    if "mlp" in params:
        out = apply_mlp(params["mlp"], h, cfg.act, gated=_gated(cfg))
    elif cfg.moe_ep:
        from ..distributed.expert_parallel import apply_moe_ep
        out = apply_moe_ep(params["moe"], h, cfg)
    else:
        out = apply_moe(params["moe"], h, cfg)
    return res + out, cache


# ===================================================================== #
# recurrent kinds: thin wrappers adding pre-norm + MLP halves
# ===================================================================== #
def init_recurrent_block(rng, cfg: ModelConfig, kind: str) -> Dict:
    dtype = _dtype(cfg)
    ks = jax.random.split(rng, 4)
    if kind == SSM:
        # Mamba2 blocks are norm + mixer only (no separate MLP)
        return {
            "pre_mix": init_norm(ks[0], cfg.d_model, cfg.norm),
            "mixer": init_ssm_block(ks[1], cfg, dtype),
        }
    p = {
        "pre_mix": init_norm(ks[0], cfg.d_model, cfg.norm),
        "mixer": init_rglru_block(ks[1], cfg, dtype),
        "pre_mlp": init_norm(ks[2], cfg.d_model, cfg.norm),
        "mlp": init_mlp(ks[3], cfg.d_model, cfg.d_ff, gated=_gated(cfg),
                        dtype=dtype),
    }
    return p


def apply_recurrent_block(params, x, cfg: ModelConfig, kind: str, *,
                          mode: str, cache: Optional[Dict] = None):
    res = x
    h = apply_norm(params["pre_mix"], x, cfg.norm, cfg.norm_eps)
    if kind == SSM:
        out, cache = apply_ssm_block(params["mixer"], h, cfg, mode=mode,
                                     cache=cache)
        return res + out, cache
    out, cache = apply_rglru_block(params["mixer"], h, cfg, mode=mode,
                                   cache=cache)
    x = res + out
    res = x
    h = apply_norm(params["pre_mlp"], x, cfg.norm, cfg.norm_eps)
    return res + apply_mlp(params["mlp"], h, cfg.act, gated=_gated(cfg)), cache


# ===================================================================== #
# dispatcher
# ===================================================================== #
def init_block(rng, cfg: ModelConfig, kind: str, *, dense_layer: bool = False
               ) -> Dict:
    if kind in _ATTN_FAMILY:
        return init_attn_block(rng, cfg, kind)
    if kind in (MLA, MLA_MOE):
        return init_mla_block(rng, cfg, kind, dense_layer)
    if kind in (SSM, RGLRU):
        return init_recurrent_block(rng, cfg, kind)
    raise ValueError(f"unknown block kind {kind!r}")


def init_block_cache(cfg: ModelConfig, kind: str, batch: int, max_len: int,
                     memory_len: int = 0) -> Optional[Dict]:
    if kind == ENC:
        return None
    if kind in (ATTN, LOCAL_ATTN, DEC):
        return init_attn_cache(cfg, kind, batch, max_len, memory_len)
    if kind in (MLA, MLA_MOE):
        return init_mla_cache(cfg, batch, max_len)
    if kind == SSM:
        return init_ssm_cache(cfg, batch, _dtype(cfg))
    if kind == RGLRU:
        return init_rglru_cache(cfg, batch, _dtype(cfg))
    raise ValueError(f"unknown block kind {kind!r}")


def apply_block(params, x, cfg: ModelConfig, kind: str, *, mode: str,
                positions=None, pos=None, cache=None, memory=None,
                layer=None):
    """``layer`` (scanned decode only): ``cache`` is stacked and the block
    returns what the step writes, for :func:`write_decode_step`."""
    if kind in _ATTN_FAMILY:
        return apply_attn_block(params, x, cfg, kind, mode=mode,
                                positions=positions, pos=pos, cache=cache,
                                memory=memory, layer=layer)
    if kind in (MLA, MLA_MOE):
        return apply_mla_block(params, x, cfg, kind, mode=mode,
                               positions=positions, pos=pos, cache=cache,
                               layer=layer)
    if kind in (SSM, RGLRU):
        if layer is not None:
            cache = jax.tree.map(lambda a: _layer_slice(a, layer), cache)
        return apply_recurrent_block(params, x, cfg, kind, mode=mode,
                                     cache=cache)
    raise ValueError(f"unknown block kind {kind!r}")


def write_decode_step(cfg: ModelConfig, kind: str, cache, written, pos):
    """Write a scanned decode step into the stacked cache, every layer at
    once: ``written`` is what :func:`apply_block` returned per layer,
    stacked ``(R, ...)`` by the scan.  K/V and latent caches take one
    position (a ring cache its slot) along the sequence axis, in place;
    recurrent states are replaced whole; cross-attention memory is kept."""
    if kind in (SSM, RGLRU):
        return written
    if kind in (MLA, MLA_MOE):
        slot, axis = pos, 2                     # (R, B, S, ·)
    else:
        slot, axis = pos, 1 + _KV_SEQ_AXIS      # (R, B, Hkv, L, Dh)
        if kind == LOCAL_ATTN and cfg.sliding_window:
            slot = _ring_slot(pos, cfg.sliding_window)
    return dict(cache, **{
        name: _write_full_cache(cache[name], new, slot, axis)
        for name, new in written.items()})
