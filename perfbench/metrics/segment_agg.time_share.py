"""Summed device time of the segment_agg kernel's trace events on the
fullest device, over the traced window."""


def read(ctx):
    if ctx.dev is None or ctx.trace.window_ns <= 0:
        return None
    busy = ctx.kernel_ns("segment_agg")
    if busy <= 0:
        return None
    return 100.0 * busy / ctx.trace.window_ns
