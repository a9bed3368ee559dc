"""Mean wait of a decode runner call for its resident cache's lock, in
ms: the engine steps each pow2 decode cell under one lock, which two
decode instances at one batch size share (``packrat.lock`` spans that
began in the traced window).  Layer: engine.  Moves ``tpot_p50_ms``."""

from bench import program_trace


def read(ctx):
    pt = program_trace.of(ctx)
    if pt is None:
        return None
    locks = pt.began_in_window("packrat.lock")
    return 1e-6 * sum(s.dur for s in locks) / len(locks) if locks else None
