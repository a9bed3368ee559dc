"""The whole decode step's share of the chip's peak, in %: the least time
its work needs at the write positions the window's calls used (the
larger of FLOPs / peak FLOP/s and bytes / peak bandwidth, from
``bench/work.py``; a batch's positions enter by their mean, exact while
the bytes bound, which is linear in the position, binds) over its device
time, summed over the decode programs the trace saw.  Layer: model step.
Moves ``tpot_p50_ms``."""

from bench import work

MODULE = "_decode"           # the engine's jitted decode program
SPAN = "bench.decode"


def read(ctx):
    if ctx.trace is None or ctx.peak is None:
        return None
    need = spent = 0.0
    for b, seconds, _ in ctx.trace.steps(MODULE, SPAN):
        pos = ctx.decode_pos.get(b)
        if b and pos:
            mean = sum(pos) / len(pos)
            need += work.decode(ctx.dims, b, mean).least_s(
                ctx.peak["flops"], ctx.peak["bytes_per_s"])
            spent += seconds
    return 100.0 * need / spent if spent else None
