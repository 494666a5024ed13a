"""The robust-aggregation kernels' share of their roofline on device 0:
the least time their required bytes take at the chip's HBM bandwidth (m
worker gradient shards read once, the aggregate written once, per
aggregation the steps' levels need; ``arithmetic.py``) over the summed
device time of the window's Pallas custom calls. Nothing when the trace
holds no custom call."""


def read(ctx):
    if ctx.trace["custom_s"] <= 0:
        return None
    least = ctx.facts["agg_bytes_per_chip"] / ctx.peaks["hbm_bytes_per_s"]
    return 100.0 * least / ctx.trace["custom_s"]
