"""The segment_agg kernel's share of its roofline on the fullest device:
the least time its calls could take (the larger of FLOPs over the bf16
peak and bytes over HBM bandwidth; the bytes bind at these widths) over
the summed device time of the kernel's trace events."""


def read(ctx):
    from perfbench import flops

    if ctx.dev is None or not ctx.peaks:
        return None
    busy = ctx.segment_agg_ns() * 1e-9
    calls = ctx.agg_calls()
    if busy <= 0 or not calls:
        return None
    work = sum(flops.agg_flops(e, d) for e, _, d in calls)
    moved = sum(flops.agg_bytes(e, r, d) for e, r, d in calls)
    least = max(work / ctx.peaks["bf16_flops"],
                moved / ctx.peaks["hbm_bytes_per_s"])
    return 100.0 * least / busy
