"""Device trace: the share of the slice in which no operation ran on the
device, 100 * (1 - busy_s / window_s)."""


def read(ctx, args):
    if ctx.trace is None:
        return None
    return 100.0 * (1.0 - ctx.trace["busy_s"] / ctx.trace["window_s"])
