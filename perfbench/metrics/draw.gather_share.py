"""Share of the host draw spent gathering feature rows: the summed
``eat.draw.gather`` spans inside the draws that lie wholly inside the
window, over those draws' summed ``eat.draw`` time."""


def read(ctx):
    from perfbench import spans

    draws = spans.whole(ctx.trace, spans.named(ctx.trace, "eat.draw"))
    if not draws:
        return None
    gather = spans.within(spans.named(ctx.trace, "eat.draw.gather"), draws)
    return 100.0 * sum(e - s for s, e in gather) / sum(e - s
                                                       for s, e in draws)
