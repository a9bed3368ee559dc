"""Full model assembly: embeddings → layer stack → head, for every family.

The layer stack is ``prefix + pattern × n_repeats + suffix``.  With
``cfg.scan_layers`` the pattern repeats run under ``jax.lax.scan`` with
stacked parameters (MaxText-style — O(1) HLO size in depth); otherwise
they are unrolled (used by smoke tests and by the dry-run differencing
cost analyzer).  Encoder-decoder configs (pattern ``(ENC, DEC)``) build
two stacks that share ``n_repeats``.

A scanned decode step leaves the stacked caches where they are: each
layer reads its own slice by the layer index, the scan emits only the
step's new entries, and one write per cache leaf puts every layer's new
position into the stack in place after the scan
(``blocks.write_decode_step``).  Feeding the caches through the scan as
``xs`` and rebuilding them as ``ys`` would copy every layer's whole cache
every step; carrying them in the scan's carry makes the TPU compiler
copy a stack of head dim 64 into another layout and back.

The public surface is :class:`Model` (build with :func:`build_model`):

    params                    = model.init(rng)
    hidden                    = model.forward(params, batch)   # (B,S,d)
    logits                    = model.logits(params, hidden)
    logits_last, cache        = model.prefill(params, batch)
    logits, cache             = model.decode_step(params, cache, tokens, pos)
    cache                     = model.init_cache(batch, max_len)
    batch_specs               = model.input_specs(shape)
"""

from __future__ import annotations

import dataclasses
import functools
import math
from typing import Any, Callable, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp

from ..configs.base import (ATTN, DEC, ENC, LOCAL_ATTN, MLA, MLA_MOE, RGLRU,
                            SSM, ModelConfig, ShapeConfig)
from .blocks import (apply_block, init_block, init_block_cache,
                     write_decode_step)
from .common import apply_norm, embed_init, init_norm

PyTree = Any


def _dtype(cfg: ModelConfig):
    return jnp.dtype(cfg.dtype)


# --------------------------------------------------------------------- #
# parameter construction
# --------------------------------------------------------------------- #
def init_params(cfg: ModelConfig, rng) -> Dict:
    dtype = _dtype(cfg)
    keys = jax.random.split(rng, 8)
    params: Dict = {
        "embed": embed_init(keys[0], (cfg.vocab_size, cfg.d_model),
                            dtype=dtype),
        "final_norm": init_norm(keys[1], cfg.d_model, cfg.norm),
    }
    if not cfg.tie_embeddings:
        params["head"] = embed_init(keys[2], (cfg.d_model, cfg.vocab_size),
                                    dtype=dtype)
    if cfg.is_encdec:
        params["enc_final_norm"] = init_norm(keys[3], cfg.d_model, cfg.norm)

    def init_stack(kinds: Tuple[str, ...], rng) -> List:
        ks = jax.random.split(rng, max(1, len(kinds)))
        return [init_block(ks[i], cfg, kind, dense_layer=True)
                for i, kind in enumerate(kinds)]

    params["prefix"] = init_stack(cfg.prefix, keys[4])
    params["suffix"] = init_stack(cfg.suffix, keys[5])

    if cfg.scan_layers:
        # one stacked pytree per pattern position: leaves (R, ...)
        def init_position(kind, rng):
            return jax.vmap(lambda k: init_block(k, cfg, kind))(
                jax.random.split(rng, cfg.n_repeats))
        pks = jax.random.split(keys[6], max(1, len(cfg.pattern)))
        params["pattern"] = [init_position(kind, pks[j])
                             for j, kind in enumerate(cfg.pattern)]
    else:
        layers = []
        pks = jax.random.split(keys[6], max(1, cfg.n_repeats))
        for r in range(cfg.n_repeats):
            ks = jax.random.split(pks[r], max(1, len(cfg.pattern)))
            layers.append([init_block(ks[j], cfg, kind)   # NOT dense_layer
                           for j, kind in enumerate(cfg.pattern)])
        params["pattern"] = layers
    return params


def param_count(params: PyTree) -> int:
    return sum(int(x.size) for x in jax.tree_util.tree_leaves(params))


def active_param_count(cfg: ModelConfig, params: PyTree) -> int:
    """Parameters touched per token (MoE: top_k + shared experts only)."""
    total = param_count(params)
    if cfg.moe is None:
        return total
    moe = cfg.moe
    n_moe_layers = sum(1 for k in cfg.layers if k == MLA_MOE)
    per_expert = 3 * cfg.d_model * moe.expert_ff
    inactive = n_moe_layers * (moe.n_experts - moe.top_k) * per_expert
    return total - inactive


# --------------------------------------------------------------------- #
# caches
# --------------------------------------------------------------------- #
def init_cache(cfg: ModelConfig, batch: int, max_len: int,
               memory_len: int = 0) -> Dict:
    def one(kind):
        return init_block_cache(cfg, kind, batch, max_len, memory_len)

    cache: Dict = {
        "prefix": [one(k) for k in cfg.prefix],
        "suffix": [one(k) for k in cfg.suffix],
    }
    if cfg.scan_layers:
        def stack(kind):
            c = one(kind)
            if c is None:
                return None
            return jax.tree_util.tree_map(
                lambda a: jnp.broadcast_to(a, (cfg.n_repeats, *a.shape)), c)
        cache["pattern"] = [stack(k) for k in cfg.pattern]
    else:
        cache["pattern"] = [[one(k) for k in cfg.pattern]
                            for _ in range(cfg.n_repeats)]
    return cache


# --------------------------------------------------------------------- #
# stack execution
# --------------------------------------------------------------------- #
def _block_fn(cfg, kind, *, mode, positions, pos, memory):
    """apply_block closure, optionally rematerialized (train only) and
    with the sequence-parallel activation constraint between blocks."""
    sp = cfg.seq_sharding and mode in ("train", "prefill")

    def fn(p, h, c, layer=None):
        h, c = apply_block(p, h, cfg, kind, mode=mode, positions=positions,
                           pos=pos, cache=c, memory=memory, layer=layer)
        if sp:
            from .common import shard_seq
            h = shard_seq(h)
        return h, c

    if cfg.remat and mode == "train":
        def fn_remat(p, h, c):
            out = jax.checkpoint(lambda pp, hh: fn(pp, hh, None)[0])(p, h)
            return out, None
        return fn_remat
    return fn


def _run_stack(params_list, kinds, x, cfg, *, mode, positions=None, pos=None,
               caches=None, memory=None):
    new_caches = []
    for i, kind in enumerate(kinds):
        c = caches[i] if caches is not None else None
        fn = _block_fn(cfg, kind, mode=mode, positions=positions, pos=pos,
                       memory=memory)
        x, c = fn(params_list[i], x, c)
        new_caches.append(c)
    return x, new_caches


def _run_pattern(params, x, cfg: ModelConfig, *, mode, positions=None,
                 pos=None, caches=None, memory=None,
                 kinds: Optional[Tuple[str, ...]] = None,
                 pattern_params=None):
    """Run the pattern × n_repeats segment (scanned or unrolled)."""
    kinds = kinds if kinds is not None else cfg.pattern
    stacked = pattern_params if pattern_params is not None else params["pattern"]
    if not kinds or cfg.n_repeats == 0:
        return x, caches
    if not cfg.scan_layers:
        new_caches = []
        for r in range(cfg.n_repeats):
            x, cs = _run_stack(stacked[r], kinds, x, cfg, mode=mode,
                               positions=positions, pos=pos,
                               caches=caches[r] if caches else None,
                               memory=memory)
            new_caches.append(cs)
        return x, new_caches

    if mode == "decode":
        fns = [_block_fn(cfg, kind, mode=mode, positions=positions,
                         pos=pos, memory=memory) for kind in kinds]

        def step(h, xs):
            layer_params, layer = xs
            written = []
            for j, fn in enumerate(fns):
                h, w = fn(layer_params[j], h, caches[j], layer)
                written.append(w)
            return h, tuple(written)

        layers = jnp.arange(cfg.n_repeats, dtype=jnp.int32)
        x, written = jax.lax.scan(step, x, (tuple(stacked), layers))
        return x, [write_decode_step(cfg, kind, c, w, pos)
                   for kind, c, w in zip(kinds, caches, written)]

    has_cache = caches is not None and mode != "train"

    def body(carry, xs):
        h = carry
        if has_cache:
            layer_params, layer_caches = xs
        else:
            layer_params, layer_caches = xs, [None] * len(kinds)
        outs = []
        for j, kind in enumerate(kinds):
            fn = _block_fn(cfg, kind, mode=mode, positions=positions,
                           pos=pos, memory=memory)
            h, c = fn(layer_params[j], h, layer_caches[j])
            outs.append(c)
        return h, tuple(outs) if has_cache else None

    xs = (tuple(stacked), tuple(caches)) if has_cache else tuple(stacked)
    x, ys = jax.lax.scan(body, x, xs)
    return x, (list(ys) if has_cache else caches)


# --------------------------------------------------------------------- #
# embeddings / head
# --------------------------------------------------------------------- #
def embed_tokens(params, tokens, cfg: ModelConfig):
    x = params["embed"][tokens].astype(_dtype(cfg))
    if cfg.scale_embedding:
        x = x * jnp.asarray(math.sqrt(cfg.d_model), _dtype(cfg))
    return x


def head_weights(params, cfg: ModelConfig):
    if cfg.tie_embeddings:
        return params["embed"].T
    return params["head"]


def apply_head(params, hidden, cfg: ModelConfig):
    logits = (hidden @ head_weights(params, cfg)).astype(jnp.float32)
    if cfg.logit_softcap:
        logits = jnp.tanh(logits / cfg.logit_softcap) * cfg.logit_softcap
    return logits


def _assemble_inputs(params, batch, cfg: ModelConfig):
    """tokens (+ modality prefix) → embedded sequence (B, S, d)."""
    x = embed_tokens(params, batch["tokens"], cfg)
    if cfg.frontend is not None and cfg.frontend.kind == "vision" \
            and "vision_embeds" in batch:
        x = jnp.concatenate(
            [batch["vision_embeds"].astype(x.dtype), x], axis=1)
    return x


# --------------------------------------------------------------------- #
# forward passes
# --------------------------------------------------------------------- #
def _decoder_positions(x):
    B, S = x.shape[0], x.shape[1]
    return jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32)[None], (B, S))


def _split_encdec(cfg: ModelConfig):
    enc_kinds = tuple(k for k in cfg.pattern if k == ENC)
    dec_kinds = tuple(k for k in cfg.pattern if k == DEC)
    return enc_kinds, dec_kinds


def _encdec_pattern_params(params, cfg: ModelConfig):
    """Split the interleaved (ENC, DEC) pattern params into two stacks."""
    enc_idx = [j for j, k in enumerate(cfg.pattern) if k == ENC]
    dec_idx = [j for j, k in enumerate(cfg.pattern) if k == DEC]
    if cfg.scan_layers:
        return ([params["pattern"][j] for j in enc_idx],
                [params["pattern"][j] for j in dec_idx])
    enc = [[layer[j] for j in enc_idx] for layer in params["pattern"]]
    dec = [[layer[j] for j in dec_idx] for layer in params["pattern"]]
    return enc, dec


def _dec_caches(caches, cfg: ModelConfig):
    """Select the DEC positions from a full-pattern cache structure."""
    dec_idx = [j for j, k in enumerate(cfg.pattern) if k == DEC]
    if cfg.scan_layers:
        return [caches[j] for j in dec_idx]
    return [[layer[j] for j in dec_idx] for layer in caches]


def _merge_dec_caches(dec_caches, cfg: ModelConfig):
    """Re-assemble a full-pattern cache list (None at ENC positions)."""
    out_one = [None] * len(cfg.pattern)
    dec_idx = [j for j, k in enumerate(cfg.pattern) if k == DEC]
    if cfg.scan_layers:
        merged = list(out_one)
        for i, j in enumerate(dec_idx):
            merged[j] = dec_caches[i]
        return merged
    merged = []
    for layer in dec_caches:
        row = list(out_one)
        for i, j in enumerate(dec_idx):
            row[j] = layer[i]
        merged.append(row)
    return merged


def encode(params, batch, cfg: ModelConfig):
    """Encoder stack over precomputed frame embeddings (audio stub)."""
    mem = batch["frames"].astype(_dtype(cfg))
    positions = _decoder_positions(mem)
    enc_params, _ = _encdec_pattern_params(params, cfg)
    mem, _ = _run_pattern(params, mem, cfg, mode="train",
                          positions=positions, kinds=(ENC,) * 1,
                          pattern_params=enc_params)
    return apply_norm(params["enc_final_norm"], mem, cfg.norm, cfg.norm_eps)


def forward(params, batch, cfg: ModelConfig):
    """Full-sequence forward → final hidden states (B, S, d)."""
    mode = "train"
    memory = encode(params, batch, cfg) if cfg.is_encdec else None
    x = _assemble_inputs(params, batch, cfg)
    positions = _decoder_positions(x)
    x, _ = _run_stack(params["prefix"], cfg.prefix, x, cfg, mode=mode,
                      positions=positions, memory=memory)
    if cfg.is_encdec:
        _, dec_params = _encdec_pattern_params(params, cfg)
        x, _ = _run_pattern(params, x, cfg, mode=mode, positions=positions,
                            memory=memory, kinds=(DEC,) * 1,
                            pattern_params=dec_params)
    else:
        x, _ = _run_pattern(params, x, cfg, mode=mode, positions=positions,
                            memory=memory)
    x, _ = _run_stack(params["suffix"], cfg.suffix, x, cfg, mode=mode,
                      positions=positions, memory=memory)
    return apply_norm(params["final_norm"], x, cfg.norm, cfg.norm_eps)


def prefill(params, batch, cfg: ModelConfig, max_len: Optional[int] = None):
    """Process the prompt, build the cache, return last-token logits."""
    mode = "prefill"
    memory = encode(params, batch, cfg) if cfg.is_encdec else None
    x = _assemble_inputs(params, batch, cfg)
    B, S = x.shape[0], x.shape[1]
    max_len = max_len or S
    mem_len = memory.shape[1] if memory is not None else 0
    cache = init_cache(cfg, B, max_len, mem_len)
    positions = _decoder_positions(x)

    x, pc = _run_stack(params["prefix"], cfg.prefix, x, cfg, mode=mode,
                       positions=positions, caches=cache["prefix"],
                       memory=memory)
    if cfg.is_encdec:
        _, dec_params = _encdec_pattern_params(params, cfg)
        x, qc = _run_pattern(params, x, cfg, mode=mode, positions=positions,
                             caches=_dec_caches(cache["pattern"], cfg),
                             memory=memory, kinds=(DEC,),
                             pattern_params=dec_params)
        qc = _merge_dec_caches(qc, cfg)
    else:
        x, qc = _run_pattern(params, x, cfg, mode=mode, positions=positions,
                             caches=cache["pattern"], memory=memory)
    x, sc = _run_stack(params["suffix"], cfg.suffix, x, cfg, mode=mode,
                       positions=positions, caches=cache["suffix"],
                       memory=memory)
    cache = {"prefix": pc, "pattern": qc, "suffix": sc}
    hidden = apply_norm(params["final_norm"], x[:, -1:], cfg.norm,
                        cfg.norm_eps)
    return apply_head(params, hidden, cfg), cache


def decode_step(params, cache, tokens, pos, cfg: ModelConfig):
    """One decode step. tokens: (B, 1); pos: scalar int32 write position."""
    mode = "decode"
    x = embed_tokens(params, tokens, cfg)
    if cfg.scale_embedding:
        pass  # already applied in embed_tokens
    x, pc = _run_stack(params["prefix"], cfg.prefix, x, cfg, mode=mode,
                       pos=pos, caches=cache["prefix"])
    if cfg.is_encdec:
        _, dec_params = _encdec_pattern_params(params, cfg)
        x, qc = _run_pattern(params, x, cfg, mode=mode, pos=pos,
                             caches=_dec_caches(cache["pattern"], cfg),
                             kinds=(DEC,), pattern_params=dec_params)
        qc = _merge_dec_caches(qc, cfg)
    else:
        x, qc = _run_pattern(params, x, cfg, mode=mode, pos=pos,
                             caches=cache["pattern"])
    x, sc = _run_stack(params["suffix"], cfg.suffix, x, cfg, mode=mode,
                       pos=pos, caches=cache["suffix"])
    hidden = apply_norm(params["final_norm"], x, cfg.norm, cfg.norm_eps)
    logits = apply_head(params, hidden, cfg)
    return logits, {"prefix": pc, "pattern": qc, "suffix": sc}


# --------------------------------------------------------------------- #
# input specs (dry-run stand-ins; no allocation)
# --------------------------------------------------------------------- #
def input_specs(cfg: ModelConfig, shape: ShapeConfig) -> Dict[str, jax.ShapeDtypeStruct]:
    B, S = shape.global_batch, shape.seq_len
    i32 = jnp.int32
    dt = _dtype(cfg)
    specs: Dict[str, jax.ShapeDtypeStruct] = {}
    if shape.kind == "decode":
        specs["tokens"] = jax.ShapeDtypeStruct((B, 1), i32)
        return specs
    if cfg.frontend is not None and cfg.frontend.kind == "vision":
        P = cfg.frontend.n_prefix_tokens
        specs["vision_embeds"] = jax.ShapeDtypeStruct((B, P, cfg.d_model), dt)
        specs["tokens"] = jax.ShapeDtypeStruct((B, S - P), i32)
    elif cfg.is_encdec:
        n_frames = min(S, cfg.frontend.n_frames) if cfg.frontend else S
        specs["frames"] = jax.ShapeDtypeStruct((B, n_frames, cfg.d_model), dt)
        specs["tokens"] = jax.ShapeDtypeStruct((B, S), i32)
    else:
        specs["tokens"] = jax.ShapeDtypeStruct((B, S), i32)
    if shape.kind == "train":
        specs["labels"] = jax.ShapeDtypeStruct((B, S), i32)
    return specs


def cache_specs(cfg: ModelConfig, shape: ShapeConfig) -> PyTree:
    """ShapeDtypeStruct pytree of the decode cache for dry-run lowering."""
    B, S = shape.global_batch, shape.seq_len
    mem_len = (min(4096, S) if cfg.is_encdec else 0)
    return jax.eval_shape(
        lambda: init_cache(cfg, B, S, mem_len))


# --------------------------------------------------------------------- #
# model facade
# --------------------------------------------------------------------- #
@dataclasses.dataclass(frozen=True)
class Model:
    cfg: ModelConfig

    def init(self, rng) -> Dict:
        return init_params(self.cfg, rng)

    def forward(self, params, batch):
        return forward(params, batch, self.cfg)

    def logits(self, params, hidden):
        return apply_head(params, hidden, self.cfg)

    def head_weights(self, params):
        return head_weights(params, self.cfg)

    def prefill(self, params, batch, max_len: Optional[int] = None):
        return prefill(params, batch, self.cfg, max_len)

    def decode_step(self, params, cache, tokens, pos):
        return decode_step(params, cache, tokens, pos, self.cfg)

    def init_cache(self, batch: int, max_len: int, memory_len: int = 0):
        return init_cache(self.cfg, batch, max_len, memory_len)

    def input_specs(self, shape: ShapeConfig):
        return input_specs(self.cfg, shape)

    def cache_specs(self, shape: ShapeConfig):
        return cache_specs(self.cfg, shape)

    def param_specs(self):
        return jax.eval_shape(lambda: self.init(jax.random.PRNGKey(0)))


def build_model(cfg: ModelConfig) -> Model:
    return Model(cfg)
