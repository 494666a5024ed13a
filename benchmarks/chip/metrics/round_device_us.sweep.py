"""Device 0's busy time per cell-round of the traced window (us)."""


def read(ctx):
    rounds = ctx.work["amounts"]["cell_rounds_per_s"]
    return 1e6 * ctx.trace["busy_s_dev0"] / rounds
