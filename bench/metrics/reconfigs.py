"""Plan changes applied in the window, summed over the prefill and decode
pools (``PackratServer.reconfig_log`` entries added while the window was
open). Layer: control plane. Moves ``tpot_p50_ms``: on one chip a
prefill, a plan change or a compile holds the chip or the reactor while
the decode steps of every request in flight wait."""


def read(ctx):
    return ctx.reconfigs_in_window
