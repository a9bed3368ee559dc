"""Share of the traced window, in %, in which Python's collector ran: the
union of the ``packrat.gc`` spans that began in the window, cut at its
end (0 where none ran).  Every thread of the server waits on a
collection.  Layer: host runtime.  Moves ``tpot_p50_ms``."""

from bench import program_trace
from bench.tracing import covered


def read(ctx):
    pt = program_trace.of(ctx)
    if pt is None:
        return None
    lo, hi = pt.window_ns
    gcs = pt.began_in_window("packrat.gc")
    return 100.0 * covered([(s.start, s.end) for s in gcs], lo, hi) \
        / (hi - lo)
