"""Share of the window the training thread spent enqueuing compiled
programs: the union of the program's ``eat.dispatch/<program>`` spans,
clipped to the window."""


def read(ctx):
    from perfbench import spans

    return spans.window_share(ctx.trace,
                              spans.prefixed(ctx.trace, "eat.dispatch/"))
