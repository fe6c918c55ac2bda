"""Client clock less worker clock: what a first token costs outside the
worker. The worker sums, per request, the time from its handler receiving
the request to the first SSE frame with content written (/worker/stats
`metrics.first_token.ttft_s` over `.count`, cumulative). The client stamps
`sent` and `first` (lib/stats.Request, on the window's clock). Over the
requests whose first token fell between the first and the last snapshot,
lead-in included, the difference of the two means is both hops through the
frontend, the router's pick, and the client's own time, in ms.

args: {"sum": "metrics.first_token.ttft_s", "count":
"metrics.first_token.count"}. A program without the counter, fewer than
two snapshots, or no first token in the window: no value."""


def _at(stats: dict, path: str):
    node = stats
    for key in path.split("."):
        if not isinstance(node, dict) or key not in node:
            return None
        node = node[key]
    return float(node)


def read(ctx, args):
    if len(ctx.snapshots) < 2:
        return None
    (t0, first), (t1, last) = ctx.snapshots[0], ctx.snapshots[-1]
    ends = [_at(s, args[k]) for s in (first, last) for k in ("sum", "count")]
    if None in ends:
        return None
    count = ends[3] - ends[1]
    client = [r.first - r.sent for r in ctx.requests
              if r.ok and r.first is not None and r.sent is not None
              and t0 <= r.first < t1]
    if count <= 0 or not client:
        return None
    worker_mean = (ends[2] - ends[0]) / count
    return 1e3 * (sum(client) / len(client) - worker_mean)
