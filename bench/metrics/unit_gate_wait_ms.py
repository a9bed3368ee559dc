"""Mean wait of a runner call, both phases, at the plane's unit gate
before it runs, in ms (``packrat.gate`` spans that began in the traced
window).  Layer: dispatcher and plane.  Moves ``tpot_p50_ms``."""

from bench import program_trace


def read(ctx):
    pt = program_trace.of(ctx)
    if pt is None:
        return None
    gates = pt.began_in_window("packrat.gate")
    return 1e-6 * sum(s.dur for s in gates) / len(gates) if gates else None
