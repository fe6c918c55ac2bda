"""/worker/stats, first to last snapshot of the window: the growth of a sum
of counters over the growth of another, times `scale`. The totals are
cumulative since the process started, so only their deltas are read.

args: {"num": ["metrics.occupancy_sum"], "den": ["metrics.occupancy_count"],
"scale": 100}. A path that is absent reads 0 (a phase that never ran has no
entry); a denominator that did not grow gives no value."""


def _at(stats: dict, path: str) -> float:
    node = stats
    for key in path.split("."):
        if not isinstance(node, dict) or key not in node:
            return 0.0
        node = node[key]
    return float(node)


def read(ctx, args):
    if len(ctx.snapshots) < 2:
        return None
    first, last = ctx.snapshots[0][1], ctx.snapshots[-1][1]

    def grew(paths):
        return sum(_at(last, p) - _at(first, p) for p in paths)

    den = grew(args["den"])
    if den <= 0:
        return None
    return args.get("scale", 1.0) * grew(args["num"]) / den
