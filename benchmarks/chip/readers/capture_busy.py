"""The program's own account of the device's busy time, held against the
device trace over the same seconds: the traced slice's.

A worker of PR 54 on keeps a device account (observability/timeline.py,
/worker/stats `timeline.device`: a watcher thread stamps every dispatched
program's end), and samples it every 0.25 s through a capture beside the
kernels' counters (/worker/stats `trace_capture.samples[].device` =
{"busy_s", "busy_enter_s", "idle_s"} as of the sample). lib/trace_reduce.py's
slice is the capture less MARGIN_S at each end (capture_roofline.py says
why the marks make it so). This reader takes the counter at the slice's
two bounds (between two samples: linearly) and gives

    100 x the program's busy seconds inside the slice / the trace's busy_s.

args: {"counter": "busy_s" | "busy_enter_s"} (busy_s counts a program from
its dispatch's exit, busy_enter_s from its enter). No trace, no capture, or
samples without the account (every worker before PR 54) give no value."""

from lib.spec import load_reader


def read(ctx, args):
    shared = load_reader("capture_roofline")
    cap = shared._capture(ctx.snapshots) if ctx.snapshots else None
    if ctx.trace is None or cap is None:
        return None
    samples = [s for s in cap["samples"] if "device" in s]
    if len(samples) < 2 or ctx.trace["busy_s"] <= 0:
        return None
    counter = args.get("counter", "busy_s")

    def at(sample, path):
        return float(sample["device"].get(path, 0.0))

    lo, hi = shared.MARGIN_S, shared.MARGIN_S + ctx.trace["window_s"]
    busy = (shared._at_time(samples, hi, at, counter)
            - shared._at_time(samples, lo, at, counter))
    return 100.0 * busy / ctx.trace["busy_s"]
