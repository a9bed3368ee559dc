"""Decode attention's share of its roofline, in %: the least time the
attention of one new token per row over its cached positions needs
(FLOPs and bytes from shapes, ``bench/work.py``; a batch's positions
enter by their mean) over the device time of the attention operations
inside the decode programs.  The operations are found by the names
below, whatever kernel implements them.  Layer: kernels.  Moves
``tpot_p50_ms``."""

from bench import work

MODULE = "_decode"
SPAN = "bench.decode"
NAMES = ("decode_attention", "_decode_kernel")


def read(ctx):
    if ctx.trace is None or ctx.peak is None:
        return None
    need = spent = 0.0
    for b, _, ops in ctx.trace.steps(MODULE, SPAN):
        t = sum(d for n, _, d in ops if any(k in n for k in NAMES)) * 1e-9
        pos = ctx.decode_pos.get(b)
        if b and t and pos:
            mean = sum(pos) / len(pos)
            need += work.decode_attention(ctx.dims, b, mean).least_s(
                ctx.peak["flops"], ctx.peak["bytes_per_s"])
            spent += t
    return 100.0 * need / spent if spent else None
