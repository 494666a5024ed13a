"""Share of the traced window in which device 0 runs a collective (the
worker-axis exchange of gradient blocks and the FSDP parameter gathers)
and no other operation: the communication that compute does not hide.
Nothing when the trace holds no collective, so that a trace this cannot
read never shows as 0."""


def read(ctx):
    if ctx.trace["collective_s"] <= 0:
        return None
    return 100.0 * ctx.trace["collective_exposed_s"] / ctx.trace["window_s"]
