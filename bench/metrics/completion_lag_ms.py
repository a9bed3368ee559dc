"""Mean time from a runner call's end on its worker to the start of its
delivery on the reactor, in ms: ``packrat.deliver`` spans that began in
the traced window, each against the ``packrat.run`` of the same batch.
Layer: dispatcher and plane.  Moves ``tpot_p50_ms``."""

from bench import program_trace


def read(ctx):
    pt = program_trace.of(ctx)
    if pt is None:
        return None
    ends = {s.args["batch"]: s.end for s in pt.spans
            if s.name == "packrat.run"}
    lags = [s.start - ends[s.args["batch"]]
            for s in pt.began_in_window("packrat.deliver")
            if s.args["batch"] in ends]
    return 1e-6 * sum(lags) / len(lags) if lags else None
