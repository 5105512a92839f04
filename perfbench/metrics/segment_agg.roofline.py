"""The segment_agg kernel's share of its roofline on the fullest device:
the least time its calls there could take (the larger of FLOPs over the
bf16 peak and bytes over HBM bandwidth; the bytes bind at these widths)
over the summed device time of the kernel's trace events.  The kernel's
file (``kernels/segment_agg.py``) counts the calls and their work."""


def read(ctx):
    if ctx.dev is None or not ctx.peaks:
        return None
    busy = ctx.kernel_ns("segment_agg") * 1e-9
    k = ctx.kernel("segment_agg")
    calls = k.calls(ctx)
    if busy <= 0 or not calls:
        return None
    work = sum(k.agg_flops(e, d) for e, _, d in calls)
    moved = sum(k.agg_bytes(e, r, d) for e, r, d in calls)
    least = max(work / ctx.peaks["bf16_flops"],
                moved / ctx.peaks["hbm_bytes_per_s"])
    return 100.0 * least / busy
