"""The gat_agg kernel's share of its roofline on the fullest device: the
least time its calls there could take (the larger of FLOPs over the bf16
peak and bytes over HBM bandwidth) over the summed device time of the
kernel's trace events.  The kernel's file (``kernels/gat_agg.py``) counts
the calls and their work."""


def read(ctx):
    if ctx.dev is None or not ctx.peaks:
        return None
    busy = ctx.kernel_ns("gat_agg") * 1e-9
    if busy <= 0:
        return None
    k = ctx.kernel("gat_agg")
    calls = k.calls(ctx)
    if not calls:
        return None
    work = sum(k.agg_flops(e, w) for e, _, w, _ in calls)
    moved = sum(k.agg_bytes(e, r, w, h) for e, r, w, h in calls)
    least = max(work / ctx.peaks["bf16_flops"],
                moved / ctx.peaks["hbm_bytes_per_s"])
    return 100.0 * least / busy
