"""Mean duration of one host epoch draw: the program's ``eat.draw`` spans
(the prefetcher's worker, one per epoch) that lie wholly inside the
window."""


def read(ctx):
    from perfbench import spans

    draws = spans.whole(ctx.trace, spans.named(ctx.trace, "eat.draw"))
    if not draws:
        return None
    return 1e-6 * sum(e - s for s, e in draws) / len(draws)
