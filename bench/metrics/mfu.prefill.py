"""The whole prefill step's share of the chip's peak, in %: the least time
its work needs (the larger of FLOPs / peak FLOP/s and bytes / peak
bandwidth, counted from shapes in ``bench/work.py``) over its device
time, summed over the prefill programs the trace saw. Layer: model step.
Moves ``tpot_p50_ms``: on one chip a prefill, a plan change or a compile
holds the chip or the reactor while the decode steps of every request in
flight wait."""

from bench import work

MODULE = "_prefill"          # the engine's jitted prefill program
SPAN = "bench.prefill"


def read(ctx):
    if ctx.trace is None or ctx.peak is None:
        return None
    need = spent = 0.0
    for b, seconds, _ in ctx.trace.steps(MODULE, SPAN):
        if b:
            need += work.prefill(ctx.dims, b, ctx.prompt_tokens).least_s(
                ctx.peak["flops"], ctx.peak["bytes_per_s"])
            spent += seconds
    return 100.0 * need / spent if spent else None
