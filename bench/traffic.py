"""The one traffic generator: reads a mix's data file, yields requests.

A mix is a JSON file under ``bench/traffic/`` holding parameters only:

    {"phases": [{"seconds": 8, "rate_rps": 2.0},
                {"seconds": 4, "rate_rps": 6.0}],
     "prompt_tokens": 512, "output_tokens": 128, "lead_in_s": 12}

The phases repeat until the window is full; a single phase with
``"seconds": null`` covers the whole window.  Arrivals are open loop.
The optional ``lead_in_s`` offers the same mix for that long before the
window opens, so that the window sees the server loaded as it is in
steady state rather than filling up from empty (a request of 128 decode
steps lives for seconds).  The lead-in and the window each run the
phase cycle from its start; the lead-in's requests are served but not
counted.
Inside a phase of length ``L`` at rate ``r`` there are exactly
``round(r * L)`` arrivals, and their gaps are the ``n + 1`` stratified
quantiles of the exponential distribution, rescaled to sum to ``L``:
every seed gets the same multiset of gaps, and the seed only permutes
their order.  The work in a window is therefore the same for every
seed, and the tails still see Poisson-shaped bunching.  The gap
quantiles follow ``PoissonWorkload`` in ``serving/workloads.py``, which
draws them independently instead.
"""

from __future__ import annotations

import json
import pathlib
from dataclasses import dataclass
from typing import List, Optional, Tuple

import numpy as np

TRAFFIC_DIR = pathlib.Path(__file__).resolve().parent / "traffic"


@dataclass(frozen=True)
class Mix:
    name: str
    phases: Tuple[Tuple[Optional[float], float], ...]   # (seconds, rate)
    prompt_tokens: int
    output_tokens: int
    lead_in_s: float = 0.0


def load_mix(name: str, directory: pathlib.Path = TRAFFIC_DIR) -> Mix:
    path = directory / f"{name}.json"
    if not path.is_file():
        raise FileNotFoundError(f"no traffic mix {name!r} at {path}")
    raw = json.loads(path.read_text())
    phases = tuple((None if p.get("seconds") is None else float(p["seconds"]),
                    float(p["rate_rps"])) for p in raw["phases"])
    if not phases or any(r <= 0 for _, r in phases):
        raise ValueError(f"mix {name!r}: phases need positive rates")
    if len(phases) > 1 and any(s is None or s <= 0 for s, _ in phases):
        raise ValueError(f"mix {name!r}: every phase of a cycle needs "
                         f"positive seconds")
    lead = float(raw.get("lead_in_s", 0.0))
    if lead < 0:
        raise ValueError(f"mix {name!r}: lead_in_s must not be negative")
    return Mix(name, phases, int(raw["prompt_tokens"]),
               int(raw["output_tokens"]), lead)


def _phase_arrivals(start: float, length: float, rate: float,
                    rng: np.random.Generator) -> List[float]:
    n = int(round(rate * length))
    if n <= 0:
        return []
    q = (np.arange(n + 1) + 0.5) / (n + 1)
    gaps = -np.log1p(-q)
    gaps = gaps * (length / gaps.sum())
    gaps = gaps[rng.permutation(n + 1)]
    return [start + float(t) for t in np.cumsum(gaps)[:n]]


def _span_arrivals(mix: Mix, start: float, seconds: float,
                   rng: np.random.Generator) -> List[float]:
    """The phase cycle run from its start over ``[start, start + seconds)``."""
    out: List[float] = []
    t = 0.0
    while t < seconds - 1e-9:
        for length, rate in mix.phases:
            span = seconds - t if length is None else min(length, seconds - t)
            if span <= 1e-9:
                break
            out.extend(_phase_arrivals(start + t, span, rate, rng))
            t += span
    return out


def arrivals(mix: Mix, seconds: float, seed: int) -> List[float]:
    """Sorted arrival times for ``seed``: the lead-in's in
    ``[0, mix.lead_in_s)``, then the window's in
    ``[mix.lead_in_s, mix.lead_in_s + seconds)``."""
    rng = np.random.default_rng(seed)
    return (_span_arrivals(mix, 0.0, mix.lead_in_s, rng)
            + _span_arrivals(mix, mix.lead_in_s, seconds, rng))


def offered_count(mix: Mix, seconds: float) -> int:
    """Arrivals in a window of ``seconds``, the same for every seed."""
    return sum(t >= mix.lead_in_s for t in arrivals(mix, seconds, 0))


__all__ = ["Mix", "TRAFFIC_DIR", "arrivals", "load_mix", "offered_count"]
