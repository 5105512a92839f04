"""Model FLOPs of the training steps completed in the traced window, over
window x chips x the chip's bf16 peak (the default matmul precision runs
float32 dots as one bf16 pass).  Real seeds, real owned nodes and real
edges only; the evaluation forward does not count."""


def read(ctx):
    work, peak = ctx.train_flops(), ctx.peaks.get("bf16_flops")
    if work <= 0 or ctx.window_s <= 0 or not peak or ctx.dev is None:
        return None
    return 100.0 * work / (ctx.window_s * ctx.chips * peak)
