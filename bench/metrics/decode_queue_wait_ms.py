"""Mean wait of a decode step in the dispatcher's queue, in ms: over the
decode batches dispatched in the traced window, the sum of their steps'
waits (dispatch time minus the step's re-enqueue) over the steps
(``packrat.dispatch`` spans).  Layer: dispatcher and plane.  Moves
``tpot_p50_ms``."""

from bench import program_trace


def read(ctx):
    pt = program_trace.of(ctx)
    if pt is None:
        return None
    ds = pt.began_in_window("packrat.dispatch", "decode")
    n = sum(s.args["n"] for s in ds)
    return 1e-3 * sum(s.args["wait_sum_us"] for s in ds) / n if n else None
