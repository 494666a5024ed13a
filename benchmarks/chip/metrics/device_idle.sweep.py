"""Share of the traced window in which device 0 runs no operation."""


def read(ctx):
    return 100.0 * (1.0 - ctx.trace["busy_s_dev0"] / ctx.trace["window_s"])
