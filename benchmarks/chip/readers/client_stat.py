"""Client clock: one of lib/stats.end_to_end's statistics over the window's
requests, for a statistic that swings too far from run to run to stand among
the end-to-end metrics under a bound. args: {"stat": "ttft_p50_ms"}."""

from lib.stats import end_to_end


def read(ctx, args):
    return end_to_end(ctx.requests, ctx.window_s, 0.0, ctx.fail_s).get(
        args["stat"])
