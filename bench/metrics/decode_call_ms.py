"""Median host time of a decode runner call that ended in the window
(the call ends in ``block_until_ready``).  Layer: engine.  Moves
``tpot_p50_ms``."""

import statistics

from bench.tracing import in_window


def read(ctx):
    d = [s.t1 - s.t0 for s in in_window(ctx.spans, ctx.window_s, "decode")]
    return 1e3 * statistics.median(d) if d else None
