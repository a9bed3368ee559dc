"""Prefill attention's share of its roofline, in %: the least time the
causal attention of the prefill needs (FLOPs and q/k/v/o bytes from
shapes, ``bench/work.py``) over the device time of the attention
operations inside the prefill programs. The operations are found by the
names below, whatever kernel implements them. Layer: kernels. Moves
``tpot_p50_ms``: on one chip a prefill, a plan change or a compile holds
the chip or the reactor while the decode steps of every request in
flight wait."""

from bench import work

MODULE = "_prefill"
SPAN = "bench.prefill"
NAMES = ("flash", "_flash_kernel")


def read(ctx):
    if ctx.trace is None or ctx.peak is None:
        return None
    need = spent = 0.0
    for b, _, ops in ctx.trace.steps(MODULE, SPAN):
        t = sum(d for n, _, d in ops if any(k in n for k in NAMES)) * 1e-9
        if b and t:
            need += work.flash_attention(ctx.dims, b, ctx.prompt_tokens) \
                .least_s(ctx.peak["flops"], ctx.peak["bytes_per_s"])
            spent += t
    return 100.0 * need / spent if spent else None
