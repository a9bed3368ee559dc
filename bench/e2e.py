"""End-to-end metrics from the client's request records.

Every request that arrived in the window counts.  A request that had not
delivered all its tokens when the drain ended is failed: it misses every
limit, so in the latency samples it stands as the time it had waited by
the end of the drain, which is above every finished request's.  No
metric is a median of chunks: each percentile is over all requests.

``nearest_rank`` is copied from ``serving/metrics.py``.
"""

from __future__ import annotations

import math
from typing import Dict, Iterable, List, Sequence


def nearest_rank(sorted_values: Sequence[float], q: float) -> float:
    """Nearest-rank percentile (q in (0, 100]) of pre-sorted values."""
    if not sorted_values:
        return float("nan")
    if not (0.0 < q <= 100.0):
        raise ValueError(f"q must be in (0, 100], got {q}")
    rank = max(1, math.ceil(q / 100.0 * len(sorted_values)))
    return sorted_values[rank - 1]


def summarize(records: Iterable, *, decode_steps: int, window_s: float,
              drain_end: float, tokens_in_window: int) -> Dict[str, object]:
    """TTFT and TPOT samples, failures and the output rate.

    TTFT is due time to first token (prefill delivered).  TPOT is
    (last token - first token) / ``decode_steps`` per request.
    """
    ttft: List[float] = []
    tpot: List[float] = []
    failed = 0
    attempted = 0
    for r in records:
        attempted += 1
        done = r.first is not None and r.steps >= decode_steps
        if not done:
            failed += 1
        first = r.first if r.first is not None else drain_end
        ttft.append(first - r.due)
        if not done:
            last = drain_end
        else:
            last = r.last if r.last is not None else first
        tpot.append((last - first) / max(1, decode_steps))
    ttft.sort()
    tpot.sort()
    return {
        "attempted": attempted,
        "failed": failed,
        "ttft_p50_ms": nearest_rank(ttft, 50) * 1e3,
        "ttft_p95_ms": nearest_rank(ttft, 95) * 1e3,
        "tpot_p50_ms": nearest_rank(tpot, 50) * 1e3,
        "tpot_p95_ms": nearest_rank(tpot, 95) * 1e3,
        "output_tok_s": tokens_in_window / window_s,
    }


__all__ = ["nearest_rank", "summarize"]
