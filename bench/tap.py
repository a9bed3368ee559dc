"""What the timed path produced, read where the engine produces it.

The engine's runners drop their logits (they replay synthetic tokens),
so the tap keeps a few of them as the window runs:

* ``engine.jit_prefill`` is wrapped before any runner cell exists, so
  every prefill runner calls through the tap.  It records each cell's
  prompt tokens, and in the window the logits of the first call of each
  prefill batch size, for the sampled rows.
* ``engine.decode_step`` is wrapped: each decode cell steps its resident
  cache through it, with the Python write position.  In the window, the
  first visit of each sampled position keeps the logits of the sampled
  rows (one device gather of a few rows).

A decode cell ``b`` starts from a prefill of its own prompt, and every
step feeds token 0 at the next position of the cycle
``[prompt_bucket, max_seq)``.  So the logits at position ``p`` are those
of the sequence ``prompt + [0] * (p - prompt_bucket + 1)`` at its last
position, which the reference computes independently.

That is the tap's contract for every kind of cache, state that a
position does not address included.  A K/V cell keeps it by nature: a
step at ``p`` reads the positions before it, and since the cycle last
wrapped to ``prompt_bucket`` those hold the prompt and the zeros this
cycle wrote.  A cell that holds recurrent state (a state-space or
convolution state) has to restart that state from the cell's prompt
when its cycle wraps to ``prompt_bucket``, or its logits continue
another sequence than the one the reference is given.
"""

from __future__ import annotations

import threading
from dataclasses import dataclass
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np


@dataclass
class Sample:
    kind: str            # "prefill" | "decode"
    b: int               # pow2 batch of the cell
    rows: Tuple[int, ...]
    pos: int             # position of the logits (prompt_bucket - 1 for prefill)
    logits: object       # device array (len(rows), V), then numpy


def sample_rows(b: int, rng: np.random.Generator) -> Tuple[int, ...]:
    """One row from each half of a batch, so that a fault confined to
    either half shows."""
    if b == 1:
        return (0,)
    h = b // 2
    return (int(rng.integers(0, h)), int(rng.integers(h, b)))


class Tap:
    def __init__(self, engine, *, seed: int, decode_positions: Sequence[int],
                 max_batch: int) -> None:
        self.engine = engine
        self.recording = False
        self.prompt_tag: Optional[Tuple[str, int]] = None
        self.prompts: Dict[Tuple[str, int], object] = {}
        self.samples: List[Sample] = []
        # every decode call's write position, per batch, in call order
        self.decode_pos: Dict[int, List[int]] = {}
        self._seen: set = set()
        self._lock = threading.Lock()
        rng = np.random.default_rng([seed, 0x7A9])
        self.rows: Dict[int, Tuple[int, ...]] = {}
        b = 1
        while b <= max_batch:
            self.rows[b] = sample_rows(b, rng)
            b *= 2
        self.positions = frozenset(int(p) for p in decode_positions)
        self.prompt_bucket = engine.default_seq_bucket
        prefill, decode_step = engine.jit_prefill, engine.decode_step

        def tapped_prefill(params, tokens):
            out = prefill(params, tokens)
            b = int(tokens.shape[0])
            if self.prompt_tag is not None:
                self.prompts[self.prompt_tag] = tokens
            elif self.recording and self._first(("prefill", b)):
                rows = self.rows[b]
                self.samples.append(Sample(
                    "prefill", b, rows, self.prompt_bucket - 1,
                    out[0][np.asarray(rows), 0]))
            return out

        def tapped_decode(cache, tokens, pos):
            logits, cache = decode_step(cache, tokens, pos)
            b = int(tokens.shape[0])
            self.decode_pos.setdefault(b, []).append(int(pos))
            if (self.recording and int(pos) in self.positions
                    and self._first(("decode", b, int(pos)))):
                rows = self.rows[b]
                self.samples.append(Sample(
                    "decode", b, rows, int(pos),
                    logits[np.asarray(rows), 0]))
            return logits, cache

        engine.jit_prefill = tapped_prefill
        engine.decode_step = tapped_decode

    def _first(self, key) -> bool:
        with self._lock:
            if key in self._seen:
                return False
            self._seen.add(key)
            return True

    def build_cells(self, max_batch: int) -> None:
        """Build every decode and prefill cell up to ``max_batch`` through
        the engine's own runner builders, recording each cell's prompt."""
        import jax.numpy as jnp
        vocab = self.engine.cfg.vocab_size
        b = 1
        while b <= max_batch:
            self.prompt_tag = ("decode", b)
            self.engine.decode_runner(1, b)
            self.prompt_tag = ("prefill", b)
            self.engine.prefill_runner(1, b, self.prompt_bucket)
            # the row gather the window runs, compiled here
            jnp.zeros((b, 1, vocab), jnp.float32)[
                np.asarray(self.rows[b]), 0].block_until_ready()
            b *= 2
        self.prompt_tag = None

    def to_host(self) -> None:
        """Copy the samples and prompts off the device."""
        for s in self.samples:
            s.logits = np.asarray(s.logits, np.float32)
        self.prompts = {k: np.asarray(v) for k, v in self.prompts.items()}


__all__ = ["Sample", "Tap", "sample_rows"]
