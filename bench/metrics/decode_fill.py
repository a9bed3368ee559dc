"""Decode steps delivered in the window over the pow2 batch slots of the
decode runner calls that ended in it, in %: padding and redispatch both
lower it.  Layer: dispatcher and plane.  Moves ``output_tok_s``."""

from bench.tracing import in_window


def read(ctx):
    slots = sum(s.b for s in in_window(ctx.spans, ctx.window_s, "decode"))
    if not slots:
        return None
    return 100.0 * ctx.decode_steps_done / slots
