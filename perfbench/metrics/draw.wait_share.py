"""Share of the window the training thread waited for the host draw: the
union of the program's ``eat.draw_wait`` spans, clipped to the window."""


def read(ctx):
    from perfbench import spans

    return spans.window_share(ctx.trace,
                              spans.named(ctx.trace, "eat.draw_wait"))
