"""/worker/stats at the window's last snapshot: one number, times `scale`.
For a level (a maximum, a gauge), where stats_delta reads the growth of a
counter.

args: {"path": "timeline.token_time.gap_max_s", "scale": 1000}. No snapshot,
or a program without the path: no value."""


def read(ctx, args):
    if not ctx.snapshots:
        return None
    node = ctx.snapshots[-1][1]
    for key in args["path"].split("."):
        if not isinstance(node, dict) or key not in node:
            return None
        node = node[key]
    return args.get("scale", 1.0) * float(node)
