"""Share of the window, in %, in which no runner call was in flight
(union of the harness's runner spans).  Layer: dispatcher and plane.
Moves ``tpot_p50_ms``."""

from bench.tracing import covered


def read(ctx):
    busy = covered([(s.t0, s.t1) for s in ctx.spans], 0.0, ctx.window_s)
    return 100.0 * (1.0 - busy / ctx.window_s)
