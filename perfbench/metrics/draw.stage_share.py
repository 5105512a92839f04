"""Share of the host draw spent staging batches for the device: the self
time of ``eat.draw.make_batch`` (its padding, labels and host-to-device
copies, without its ``eat.draw.neighbors`` and ``eat.draw.gather``
children) plus the ``eat.draw.stack`` time, inside the draws that lie
wholly inside the window, over those draws' summed ``eat.draw`` time."""


def read(ctx):
    from perfbench import spans

    tr = ctx.trace
    draws = spans.whole(tr, spans.named(tr, "eat.draw"))
    if not draws:
        return None
    make = spans.within(spans.named(tr, "eat.draw.make_batch"), draws)
    children = sorted(spans.named(tr, "eat.draw.neighbors")
                      + spans.named(tr, "eat.draw.gather"))
    stack = spans.within(spans.named(tr, "eat.draw.stack"), draws)
    staged = spans.self_ns(make, children) + sum(e - s for s, e in stack)
    return 100.0 * staged / sum(e - s for s, e in draws)
