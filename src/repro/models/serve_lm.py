"""Autoregressive LM serving engine: ``models/lm.py`` behind the plane.

This module is the bridge from "Packrat for one-shot inference" to
"Packrat for LLM serving": it wires a decoder — the published
``gemma3-1b``, or its smoke-size reduction ``lm-tiny`` — into
:class:`~repro.serving.plane.RealPlane` behind the existing
``make_runner(t, b)`` factory contract, split into the two phases of LLM
inference with opposite resource profiles:

* **prefill** (compute-bound) — one full-prompt forward through the
  Pallas ``flash_attention`` kernel, building the KV cache.  Runner
  cells are pow2-bucketed ⟨t, b, seq-bucket⟩.
* **decode** (memory-bound) — one token for every resident sequence
  through the Pallas ``decode_attention`` kernel against the pooled KV
  cache, with **buffer donation** on the cache so each step updates it
  in place instead of copying.

The engine owns a KV-cache pool: each decode runner cell ⟨t, b⟩ keeps a
resident ⟨cache, position⟩ it advances every step, exactly the state a
continuous-batching server holds for its in-flight sequences.  Every
jitted callable is compiled inside the factory (outside the timed
path), so :class:`RealPlane`'s ``compile_ms`` accounting captures the
first-touch cost and the controller's plan-apply hook can warm cells
ahead of traffic.  A runner call's wait for its decode cell's lock, its
enqueue and its wait for the device are spans in the profiler's trace
(``packrat.lock``, ``packrat.enqueue``, ``packrat.device_wait``).

The kernels are reached through ``cfg.use_pallas_kernels`` (see
``models/blocks.py``): every serving config sets it, so serving runners,
the differential tests, and the kernel oracles all execute one code
path.
"""

from __future__ import annotations

import functools
import threading
from typing import Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from ..configs.gemma3_1b import GEMMA3_1B
from ..core import spans
from ..core.knapsack import next_power_of_two
from .lm import Model, build_model

LM_MODELS = ("lm-tiny", "gemma3-1b")

PHASE_PREFILL = "prefill"
PHASE_DECODE = "decode"
PHASES = (PHASE_PREFILL, PHASE_DECODE)


def lm_tiny_config():
    """gemma3-1b scaled to smoke size, routed through the Pallas kernels.

    float32 keeps the prefill+decode vs full-forward differential test
    tolerance tight; the layer stack keeps gemma3's 5:1 local:global
    attention mix (sliding window 64) so both the ring-cache and the
    full-cache decode paths are exercised.
    """
    return GEMMA3_1B.reduced(
        n_repeats=1, d_model=32, n_heads=2, d_ff=64, vocab_size=256,
        name="lm-tiny", dtype="float32", use_pallas_kernels=True)


def gemma3_1b_config():
    """gemma3-1b at its published widths and full depth (26 layers,
    d_model 1152, 4 q heads / 1 kv head of dim 256, sliding window 512,
    vocab 262144), bf16, routed through the Pallas kernels: about 1 B
    parameters, 2 GB, which one 16 GB v5e chip holds whole."""
    return GEMMA3_1B.with_overrides(use_pallas_kernels=True)


# name → (config builder, LmEngine defaults).  gemma3-1b's max_seq of
# 1024 exceeds its 512-token window, so decode runs against both the
# 512-slot ring caches of the local layers and the full caches of the
# global ones.
_LM_REGISTRY = {
    "lm-tiny": (lm_tiny_config, {}),
    "gemma3-1b": (gemma3_1b_config,
                  {"max_seq": 1024, "default_seq_bucket": 128}),
}


class LmEngine:
    """KV-cache pool + pow2-bucketed jitted runners for one decoder.

    ``factory()`` returns the plane-facing runner factory (marked
    ``phase_aware``: the plane passes the worker pool's phase as a third
    argument).  ``prefill``/``decode_step`` expose the same jitted
    callables functionally for the differential tests; ``jit_prefill``
    and ``jit_decode`` are those programs themselves, for lowering and
    inspection.
    """

    def __init__(self, cfg=None, *, seed: int = 0, max_seq: int = 64,
                 default_seq_bucket: int = 16) -> None:
        self.cfg = cfg if cfg is not None else lm_tiny_config()
        if not self.cfg.use_pallas_kernels:
            raise ValueError("LmEngine serves through the Pallas kernels; "
                             "cfg.use_pallas_kernels must be set")
        self.model: Model = build_model(self.cfg)
        # one compiled init program, not one dispatch per weight
        self.params = jax.jit(self.model.init)(jax.random.PRNGKey(seed))
        if max_seq < 2 or default_seq_bucket >= max_seq:
            raise ValueError(
                f"need default_seq_bucket < max_seq, got "
                f"{default_seq_bucket} vs {max_seq}")
        self.max_seq = max_seq
        self.default_seq_bucket = next_power_of_two(default_seq_bucket)
        self._rng = jax.random.PRNGKey(seed + 1)

        model, max_len = self.model, self.max_seq

        @jax.jit
        def _prefill(params, tokens):
            return model.prefill(params, {"tokens": tokens},
                                 max_len=max_len)

        # buffer donation on the cache: the decode step consumes the old
        # cache's buffers and returns them updated in place
        @functools.partial(jax.jit, donate_argnums=(1,))
        def _decode(params, cache, tokens, pos):
            return model.decode_step(params, cache, tokens, pos)

        self.jit_prefill = _prefill
        self.jit_decode = _decode
        # ⟨b⟩-keyed resident decode state: (cache, python position).
        # A step donates its cell's cache, so two workers stepping one
        # cell at once would read a deleted buffer: each cell steps under
        # its own lock.
        self._resident: Dict[int, Tuple[object, int]] = {}
        self._resident_locks: Dict[int, threading.Lock] = {}
        self._runners: Dict[Tuple[str, int, int], Callable[[], None]] = {}

    # ------------------------------------------------------------------ #
    # functional surface (differential tests)
    # ------------------------------------------------------------------ #
    def prefill(self, tokens):
        """(logits_last (B,1,V), cache) for a (B, S) prompt batch."""
        return self.jit_prefill(self.params, jnp.asarray(tokens, jnp.int32))

    def decode_step(self, cache, tokens, pos):
        """One decode step; donates ``cache`` (do not reuse the input)."""
        return self.jit_decode(self.params, cache,
                                jnp.asarray(tokens, jnp.int32),
                                jnp.asarray(pos, jnp.int32))

    # ------------------------------------------------------------------ #
    # bucketing
    # ------------------------------------------------------------------ #
    def seq_bucket(self, prompt_len: int) -> int:
        """Pow2 seq bucket for a prompt length, clamped to max_seq."""
        return min(next_power_of_two(max(1, prompt_len)), self.max_seq)

    def _sample_tokens(self, b: int, s: int):
        self._rng, k = jax.random.split(self._rng)
        return jax.random.randint(k, (b, s), 0, self.cfg.vocab_size,
                                  jnp.int32)

    # ------------------------------------------------------------------ #
    # runner cells
    # ------------------------------------------------------------------ #
    def prefill_runner(self, t: int, b: int, s: Optional[int] = None
                       ) -> Callable[[], None]:
        """Jitted prefill runner for a ⟨t, b, seq-bucket⟩ cell.  ``t``
        cannot repartition the CPU intra-op pool (see ``models/micro``):
        same-shape cells share one compiled executable across t."""
        b = next_power_of_two(max(1, b))
        s = self.seq_bucket(s if s is not None else self.default_seq_bucket)
        key = (PHASE_PREFILL, b, s)
        run = self._runners.get(key)
        if run is None:
            tokens = self._sample_tokens(b, s)
            fn, params = self.jit_prefill, self.params
            jax.block_until_ready(fn(params, tokens))   # compile here

            def run() -> None:
                with spans.span("packrat.enqueue", phase=PHASE_PREFILL,
                                b=b):
                    out = fn(params, tokens)
                with spans.span("packrat.device_wait", phase=PHASE_PREFILL,
                                b=b):
                    jax.block_until_ready(out)

            self._runners[key] = run
        return run

    def decode_runner(self, t: int, b: int) -> Callable[[], None]:
        """Jitted decode runner for a ⟨t, b⟩ cell over its resident
        KV-cache pool: each call advances every resident sequence by one
        token, donating the cache.  The resident position wraps inside
        [seq_bucket, max_seq) so the cell serves indefinitely."""
        b = next_power_of_two(max(1, b))
        key = (PHASE_DECODE, b, 0)
        run = self._runners.get(key)
        if run is None:
            s0 = self.default_seq_bucket
            _, cache = self.prefill(self._sample_tokens(b, s0))
            self._resident[b] = (cache, s0)
            lock = self._resident_locks.setdefault(b, threading.Lock())
            engine = self

            def step() -> None:
                with spans.span("packrat.lock", b=b):
                    lock.acquire()
                try:
                    cache, pos = engine._resident[b]
                    with spans.span("packrat.enqueue", phase=PHASE_DECODE,
                                    b=b):
                        tokens = jnp.zeros((b, 1), jnp.int32)
                        logits, cache = engine.decode_step(cache, tokens,
                                                           pos)
                    with spans.span("packrat.device_wait",
                                    phase=PHASE_DECODE, b=b):
                        logits.block_until_ready()
                    nxt = s0 + (pos - s0 + 1) % (engine.max_seq - s0)
                    engine._resident[b] = (cache, nxt)
                finally:
                    lock.release()

            step()                                       # compile here

            def run() -> None:
                step()

            self._runners[key] = run
        return run

    # ------------------------------------------------------------------ #
    # plane-facing factory
    # ------------------------------------------------------------------ #
    def factory(self, *, seq_bucket: Optional[int] = None):
        """The plane's ``RunnerFactory``, phase-aware: ``make(t, b,
        phase)`` routes "prefill" to the ⟨t, b, seq-bucket⟩ prefill cell
        and everything else to the decode pool."""
        s = self.seq_bucket(seq_bucket if seq_bucket is not None
                            else self.default_seq_bucket)

        def make(t: int, b: int, phase: str = PHASE_DECODE
                 ) -> Callable[[], None]:
            if phase == PHASE_PREFILL:
                return self.prefill_runner(t, b, s)
            return self.decode_runner(t, b)

        make.phase_aware = True
        return make


def make_lm_engine(name: str = "lm-tiny", *, seed: int = 0, **kw) -> LmEngine:
    """Engine for one registered LM serving model; ``kw`` overrides the
    model's registered engine defaults."""
    if name not in LM_MODELS:
        raise ValueError(f"unknown LM serving model {name!r}; "
                         f"choose from {sorted(LM_MODELS)}")
    make_cfg, defaults = _LM_REGISTRY[name]
    return LmEngine(make_cfg(), seed=seed, **{**defaults, **kw})


__all__ = ["LM_MODELS", "LmEngine", "PHASES", "PHASE_DECODE",
           "PHASE_PREFILL", "gemma3_1b_config", "lm_tiny_config",
           "make_lm_engine"]
