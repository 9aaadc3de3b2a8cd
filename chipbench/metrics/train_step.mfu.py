"""The whole step's share of the chips' peak: model FLOPs per sample (the
configuration's count of forward and backward, no clipping work, no
recomputation) times the samples per second of the traced window, over the
chips' bf16 peak.  A kernel taken off the path leaves its roofline silent;
this share still bounds what a change gained."""
NAME = "train_step.mfu"


def read(ctx):
    w = ctx.window
    flops = ctx.config.model_flops_per_sample(ctx.cfg, ctx.wl)
    return 100.0 * flops * (w.samples / w.window_s) / (ctx.chips * ctx.peaks.flops)
