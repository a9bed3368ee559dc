"""Median host time of a decode step's enqueue, in ms: the jitted call up
to its return, before the wait for the device (``packrat.enqueue`` spans
of the decode phase that began in the traced window).  Layer: engine.
Moves ``tpot_p50_ms``."""

import statistics

from bench import program_trace


def read(ctx):
    pt = program_trace.of(ctx)
    if pt is None:
        return None
    d = [s.dur for s in pt.began_in_window("packrat.enqueue", "decode")]
    return 1e-6 * statistics.median(d) if d else None
