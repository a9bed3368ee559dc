"""Median host time of a prefill runner call that ended in the window (the
call ends in ``block_until_ready``). Layer: engine. Moves
``tpot_p50_ms``: on one chip a prefill, a plan change or a compile holds
the chip or the reactor while the decode steps of every request in
flight wait."""

import statistics

from bench.tracing import in_window


def read(ctx):
    d = [s.t1 - s.t0 for s in in_window(ctx.spans, ctx.window_s, "prefill")]
    return 1e3 * statistics.median(d) if d else None
