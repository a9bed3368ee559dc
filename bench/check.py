"""The comparison that decides ``correct``.

For each logit row the tap kept (a sampled row of a prefill cell at the
prompt's last position, or of a decode cell at a sampled position), the
reference computes the logits of the same token sequence.  Two numbers
are computed, each compared against its own limit from the
configuration file's ``limits`` group where it has one:

* ``gap`` — the widest gap by which the logit of the token the program
  ranks first lies below the reference's best logit (a greedy server
  would serve that token);
* ``err`` — the widest ``max |program - reference| / max |reference|``
  over the compared positions.

``control`` computes the same two numbers for the fp8 control in the
program's place, at the same sequences and positions.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Dict, List, Sequence, Tuple

import numpy as np

from bench.reference import logits_at


@dataclass
class Rows:
    """The sequences to compare, each with its positions, and the
    program's logits at them."""
    sequences: List[np.ndarray]
    positions: List[List[int]]
    program: List[np.ndarray]


def gather(samples, prompts: Dict[Tuple[str, int], np.ndarray],
           prompt_bucket: int) -> Rows:
    """Group the tap's samples by the token sequence they continue."""
    by_seq: Dict[Tuple[str, int, int], Dict[int, np.ndarray]] = {}
    for s in samples:
        for j, r in enumerate(s.rows):
            by_seq.setdefault((s.kind, s.b, r), {})[s.pos] = s.logits[j]
    seqs, poss, progs = [], [], []
    for (kind, b, r), at in sorted(by_seq.items()):
        positions = sorted(at)
        prompt = np.asarray(prompts[(kind, b)][r], np.int32)
        tail = max(0, positions[-1] + 1 - prompt_bucket)
        seqs.append(np.concatenate([prompt, np.zeros(tail, np.int32)]))
        poss.append(positions)
        progs.append(np.stack([at[p] for p in positions]))
    return Rows(seqs, poss, progs)


def compare(candidate: Sequence[np.ndarray], reference: Sequence[np.ndarray]
            ) -> Dict[str, float]:
    gap = err = 0.0
    n = 0
    for cand, ref in zip(candidate, reference):
        top = np.argmax(cand, axis=-1)
        best = ref.max(axis=-1)
        picked = np.take_along_axis(ref, top[:, None], axis=-1)[:, 0]
        gap = max(gap, float(np.max(best - picked)))
        scale = np.max(np.abs(ref), axis=-1)
        err = max(err, float(np.max(np.max(np.abs(cand - ref), axis=-1)
                                    / scale)))
        n += len(ref)
    return {"gap": gap, "err": err, "tokens": n}


def check(rows: Rows, seed: int, published: Dict) -> Dict[str, float]:
    """The program's numbers against the float32 reference."""
    ref = logits_at(seed, published, rows.sequences, rows.positions)
    return compare(rows.program, ref)


def control(rows: Rows, seed: int, published: Dict) -> Dict[str, float]:
    """The fp8 control's numbers against the float32 reference."""
    ref = logits_at(seed, published, rows.sequences, rows.positions)
    low = logits_at(seed, published, rows.sequences, rows.positions,
                    precision="fp8")
    return compare(low, ref)


def verdict(numbers: Dict[str, float], limits: Dict[str, float],
            min_tokens: int) -> Tuple[bool, List[Tuple[str, float, object]]]:
    """(correct, [(name, number, limit), ...]).  A number compared only
    where the configuration gives it a limit (one that does not separate
    the program from the control has none); too few compared tokens is
    not correct, nor is a configuration with no limit at all."""
    lines = [(k, numbers[k], limits.get(k)) for k in ("gap", "err")]
    lines.append(("tokens", numbers["tokens"], min_tokens))
    compared = [(v, lim) for _, v, lim in lines[:2] if lim is not None]
    ok = bool(compared) and all(v <= lim for v, lim in compared) \
        and numbers["tokens"] >= min_tokens
    return ok, lines


__all__ = ["Rows", "check", "compare", "control", "gather", "verdict"]
