"""Device trace: time in the operations whose name matches `pattern`, as a
percentage of the device's busy time in the slice. The pattern is read off
one real trace by hand and kept in the metric's file. No matching operation
gives no value: the pattern, or the kernel, has gone."""

from lib.trace_reduce import time_matching


def read(ctx, args):
    if ctx.trace is None:
        return None
    t = time_matching(ctx.trace, args["pattern"])
    return 100.0 * t / ctx.trace["busy_s"] if t > 0 else None
