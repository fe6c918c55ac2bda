#!/usr/bin/env python3
"""From a kept trace to the device's longest idle gaps, each put down to the
phase of the engine thread that covers most of it. Pure arithmetic on plain
data, like trace_reduce.py, so that it can be checked on a small recorded
trace beside the tests.

While the worker takes a profiler capture (GET /debug/trace), every segment of
the stepline (dynamo_tpu/observability/timeline.py) is also a profiler
annotation named `stepline/<segment>`: admit, page_alloc, dispatch,
device_wait, detok, bank, untracked, between_steps, no_work. They land on a
line of the host plane, on the same clock as the device plane's operations
line. A gap of that line is a time the device ran nothing; the annotations
that overlap it say what the host was doing meanwhile.

The input is what trace_extract.py writes when its plane pattern is widened
to take the host plane too (run.py --keep-trace leaves the unpacked trace in
chiprun_out/bench/trace/):

    python benchmarks/chip/trace_extract.py chiprun_out/bench/trace \
        '^/device:TPU:\\d+$|^/host:CPU$' wide.json
    python benchmarks/chip/lib/gaps.py wide.json

Nothing in run.py calls this yet: `breakdown.idle_gaps` needs an edit there.
"""

from __future__ import annotations

import bisect
import json
import os
import re
import sys

if __package__:
    from .trace_reduce import TraceError, _device_lines
else:       # run as a script
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    from lib.trace_reduce import TraceError, _device_lines

PREFIX = "stepline/"
STEP = PREFIX + "step"      # the step's own annotation holds its segments
UNCOVERED = "(no annotation)"


def busy_intervals(events) -> list:
    """The union of (start_ns, dur_ns, _) events as sorted, disjoint
    [start_ns, end_ns] pairs."""
    out = []
    for start, end in sorted((s, s + d) for s, d, _ in events):
        if out and start <= out[-1][1]:
            out[-1][1] = max(out[-1][1], end)
        else:
            out.append([start, end])
    return out


def stepline_segments(trace: dict, host_plane_re: str) -> list:
    """[(start_ns, end_ns, segment name)] of every `stepline/<segment>`
    event of the host planes, sorted; the step's own annotation is left out
    (it contains its segments)."""
    names = trace["names"]
    out = []
    for plane in trace["planes"]:
        if not re.search(host_plane_re, plane["name"]):
            continue
        for line in plane["lines"]:
            for start, dur, idx in line["events"]:
                name = names[idx]
                if name.startswith(PREFIX) and name != STEP:
                    out.append((start, start + dur, name[len(PREFIX):]))
    return sorted(out)


def idle_gaps(trace: dict, plane_re: str, line_re: str,
              host_plane_re: str = r"^/host:CPU$", margin_s: float = 0.25,
              top: int = 10) -> dict:
    """{"window_s", "idle_s", "gaps", "idle_by_phase_s", "longest":
    [{"plane", "at_ms", "gap_ms", "phase", "phase_ms", "by_ms"}]}.

    The slice is trace_reduce.reduce()'s: the span of the operations line
    over all device planes, moved in by `margin_s` at both ends. A gap is
    a maximal time inside the slice in which one device plane's operations
    line runs nothing. `phase` is the stepline segment that overlaps the
    gap longest, `by_ms` all of them (time that no annotation covers is
    "(no annotation)"); `idle_by_phase_s` adds `by_ms` up over every gap of
    the slice, the trace's own account of the idle time by host phase.
    Seconds are averaged over the device planes, as busy_s is."""
    planes = _device_lines(trace, plane_re, line_re)
    if not planes or not all(lines for _, lines in planes):
        raise TraceError(f"no device operation on line {line_re!r} of a "
                         f"plane matching {plane_re!r}")
    every = [ev for _, lines in planes for ln in lines for ev in ln]
    margin = int(margin_s * 1e9)
    lo = min(ev[0] for ev in every) + margin
    hi = max(ev[0] + ev[1] for ev in every) - margin
    if hi <= lo:
        raise TraceError("the trace is too short for its margins")
    segments = stepline_segments(trace, host_plane_re)
    starts = [s0 for s0, _, _ in segments]
    gaps = []
    for plane_name, lines in planes:
        busy = busy_intervals([ev for ln in lines for ev in ln])
        edge = lo
        for start, end in busy + [[hi, hi]]:
            if min(start, hi) > edge:
                gaps.append((plane_name, edge, min(start, hi)))
            edge = max(edge, end)
            if edge >= hi:
                break
    by_phase, rows = {}, []
    for plane_name, g0, g1 in gaps:
        by = {}
        # one thread's segments follow each other: only the one before the
        # first that starts inside the gap can reach into it
        first = max(0, bisect.bisect_left(starts, g0) - 1)
        for s0, s1, name in segments[first:]:
            if s0 >= g1:
                break
            over = min(s1, g1) - max(s0, g0)
            if over > 0:
                by[name] = by.get(name, 0) + over
        rest = (g1 - g0) - sum(by.values())
        if rest > 0:
            by[UNCOVERED] = rest
        for name, ns in by.items():
            by_phase[name] = by_phase.get(name, 0) + ns
        phase = max(by, key=by.get)
        rows.append({
            "plane": plane_name, "at_ms": (g0 - lo) / 1e6,
            "gap_ms": (g1 - g0) / 1e6, "phase": phase,
            "phase_ms": by[phase] / 1e6,
            "by_ms": {n: ns / 1e6 for n, ns in sorted(
                by.items(), key=lambda kv: -kv[1])}})
    chips = len(planes)
    rows.sort(key=lambda r: -r["gap_ms"])
    return {
        "window_s": (hi - lo) / 1e9,
        "idle_s": sum(g1 - g0 for _, g0, g1 in gaps) / 1e9 / chips,
        "gaps": len(gaps),
        "stepline_events": len(segments),
        "idle_by_phase_s": {n: ns / 1e9 / chips for n, ns in sorted(
            by_phase.items(), key=lambda kv: -kv[1])},
        "longest": rows[:top],
    }


def cut(trace: dict, plane_re: str, line_re: str, host_plane_re: str,
        start_s: float, length_s: float) -> dict:
    """A small trace for the tests: the operations line of the first device
    plane and the stepline annotations, `length_s` seconds from `start_s`
    after the first device operation, with only the names they use."""
    planes = _device_lines(trace, plane_re, line_re)
    t0 = min(ev[0] for _, lines in planes for ln in lines for ev in ln)
    lo = t0 + int(start_s * 1e9)
    hi = lo + int(length_s * 1e9)
    names, used, out = trace["names"], {}, []

    def keep(events, want):
        return [[s, d, used.setdefault(i, len(used))]
                for s, d, i in sorted(events)
                if s + d > lo and s < hi and want(names[i])]

    for plane in trace["planes"]:
        device = re.search(plane_re, plane["name"])
        if not device and not re.search(host_plane_re, plane["name"]):
            continue
        if device and any(re.search(plane_re, p["name"]) for p in out):
            continue
        lines = []
        for line in plane["lines"]:
            if device and not re.search(line_re, line["name"]):
                continue
            events = keep(line["events"], (lambda n: True) if device
                          else (lambda n: n.startswith(PREFIX)))
            if events:
                lines.append({"name": line["name"], "events": events})
        out.append({"name": plane["name"], "lines": lines})
    by_idx = sorted(used, key=used.get)
    return {"names": [names[i] for i in by_idx], "planes": out,
            "planes_seen": trace.get("planes_seen", [])}


def table(result: dict) -> str:
    rows = ["| at ms | gap ms | phase | its ms | others |", "|---|---|---|---|---|"]
    for r in result["longest"]:
        others = ", ".join(f"{n} {ms:.2f}" for n, ms in r["by_ms"].items()
                           if n != r["phase"])
        rows.append(f"| {r['at_ms']:.1f} | {r['gap_ms']:.2f} | {r['phase']} "
                    f"| {r['phase_ms']:.2f} | {others or '-'} |")
    return "\n".join(rows)


def main(argv) -> int:
    import argparse

    from lib.spec import load_device

    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("events", help="trace_extract.py's output, host plane "
                                  "included")
    p.add_argument("--device-kind", default="TPU v5 lite")
    p.add_argument("--host-plane", default=r"^/host:CPU$")
    p.add_argument("--top", type=int, default=10)
    p.add_argument("--margin-s", type=float, default=0.25)
    p.add_argument("--cut", metavar="OUT.json",
                   help="write a small trace for the tests instead")
    p.add_argument("--cut-start-s", type=float, default=1.0)
    p.add_argument("--cut-length-s", type=float, default=0.1)
    args = p.parse_args(argv)
    device = load_device(args.device_kind)
    with open(args.events) as f:
        trace = json.load(f)
    if args.cut:
        small = cut(trace, device["trace_plane"], device["trace_ops_line"],
                    args.host_plane, args.cut_start_s, args.cut_length_s)
        with open(args.cut, "w") as f:
            json.dump(small, f)
        return 0
    result = idle_gaps(trace, device["trace_plane"], device["trace_ops_line"],
                       args.host_plane, args.margin_s, args.top)
    lines = {p["name"]: [ln["name"] for ln in p["lines"] if any(
                 trace["names"][i].startswith(PREFIX)
                 for _, _, i in ln["events"])]
             for p in trace["planes"]
             if re.search(args.host_plane, p["name"])}
    print(json.dumps({**result, "annotation_lines": lines}, indent=1))
    print(table(result))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
