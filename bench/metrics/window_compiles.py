"""Programs JAX compiled, or loaded from its persistent cache, while the
window was open (its ``backend_compile_duration`` events): a runner cell
first built in the window, or any other program first run there. Set-up
builds every cell and warms every shape, so this should be 0. Layer:
control plane. Moves ``tpot_p50_ms``: on one chip a prefill, a plan
change or a compile holds the chip or the reactor while the decode steps
of every request in flight wait."""


def read(ctx):
    return len(ctx.compiles_in_window)
