"""Share of the traced window in which no operation ran on the fullest
device: 1 - (union of its operation intervals) / window."""


def read(ctx):
    if ctx.trace.window_ns <= 0 or ctx.dev is None:
        return None
    return 100.0 * (1.0 - ctx.trace.busy_ns(ctx.dev) / ctx.trace.window_ns)
