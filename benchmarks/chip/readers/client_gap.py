"""Client clock: a percentile over the window's requests of each request's
largest gap between two streamed tokens (a prompt chunk stalling decode
shows here). args: {"quantile": 95}."""

from lib.stats import percentile


def read(ctx, args):
    gaps = [r.gap_max * 1e3 for r in ctx.requests
            if r.phase == "window" and r.ok and len(r.frame_times) >= 2]
    if not gaps:
        return None
    return percentile(gaps, args["quantile"])
