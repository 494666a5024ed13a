"""Model FLOP/s utilization of training: the forward and backward
operations the window's tokens require (``arithmetic.py``, MLMC's repeated
prefixes not counted), over the traced window times the chips' bf16 peak."""


def read(ctx):
    peak = ctx.peaks["bf16_flops_per_s"] * ctx.chips
    return 100.0 * ctx.facts["train_flops"] / (ctx.trace["window_s"] * peak)
