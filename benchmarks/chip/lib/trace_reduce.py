"""From the events of a profiler trace to device busy time and time by
operation. Pure arithmetic on plain data, so that it can be checked on the
small recorded trace beside the tests.

A trace here is what trace_extract.py writes:

    {"names": [str, ...],
     "planes": [{"name": str,
                 "lines": [{"name": str,
                            "events": [[start_ns, dur_ns, name_idx], ...]}]}]}

Busy time is the union of the intervals of ONE line of each device plane (the
operations line: a device plane's module, step and operation lines overlap,
and summing them overshoots the window), clipped to the slice. The slice is
the span of that line's events over all device planes, moved in by a margin
at both ends, where the profiler starts and stops. So 0 < busy_s <= window_s
holds by construction; reduce() asserts it all the same.
"""

from __future__ import annotations

import re


class TraceError(Exception):
    pass


def union_s(intervals) -> float:
    """Total length in seconds of the union of (start_ns, end_ns) pairs."""
    total, cur_s, cur_e = 0, None, None
    for s, e in sorted(intervals):
        if cur_e is None or s > cur_e:
            if cur_e is not None:
                total += cur_e - cur_s
            cur_s, cur_e = s, e
        elif e > cur_e:
            cur_e = e
    if cur_e is not None:
        total += cur_e - cur_s
    return total / 1e9


def self_times(events) -> list:
    """[(name_idx, self_ns)] of one line's (start, end, name_idx) events: an
    operation's own time is its length less its children's. On the
    operations line a `while` or a fusion's parent contains the operations
    it ran, so summing lengths would count that time twice."""
    out, stack = [], []          # stack of [end, name_idx, self_ns]

    def close(until):
        while stack and stack[-1][0] <= until:
            end, idx, own = stack.pop()
            out.append((idx, own))

    for start, end, idx in sorted(events, key=lambda e: (e[0], -e[1])):
        close(start)
        if stack:
            end = min(end, stack[-1][0])     # a child never outlives its parent
            stack[-1][2] -= end - start
        stack.append([end, idx, end - start])
    close(float("inf"))
    return out


def _device_lines(trace: dict, plane_re: str, line_re: str) -> list:
    """[(plane name, [[events of one matching line], ...])] per device
    plane."""
    out = []
    for plane in trace["planes"]:
        if not re.search(plane_re, plane["name"]):
            continue
        lines = [[ev for ev in line["events"] if ev[1] > 0]
                 for line in plane["lines"]
                 if re.search(line_re, line["name"])]
        out.append((plane["name"], [ln for ln in lines if ln]))
    return out


def reduce(trace: dict, plane_re: str, line_re: str,
           margin_s: float = 0.25, top: int = 10) -> dict:
    """{"window_s", "busy_s", "chips", "op_s": {name: seconds}, "top_ops"}:
    seconds averaged over the device planes, everything clipped to the
    slice; an operation's seconds are its own time (self_times)."""
    planes = _device_lines(trace, plane_re, line_re)
    if not planes:
        raise TraceError(f"no plane matches {plane_re!r}: "
                         f"{[p['name'] for p in trace['planes']]}")
    if not all(lines for _, lines in planes):
        empty = [name for name, lines in planes if not lines]
        raise TraceError(f"no device operation on line {line_re!r} of "
                         f"{empty}: the device plane is empty")
    every = [ev for _, lines in planes for ln in lines for ev in ln]
    first = min(ev[0] for ev in every)
    last = max(ev[0] + ev[1] for ev in every)
    margin = int(margin_s * 1e9)
    lo, hi = first + margin, last - margin
    if hi - lo < 2 * margin:
        raise TraceError(
            f"the trace spans {(last - first) / 1e9:.3f}s: too short for a "
            f"slice with {margin_s}s cut from each end")
    names = trace["names"]
    busy, op_ns = 0.0, {}
    for _, lines in planes:
        spans = []
        for events in lines:
            clipped = [(max(start, lo), min(start + dur, hi), idx)
                       for start, dur, idx in events
                       if min(start + dur, hi) > max(start, lo)]
            spans.extend((s, e) for s, e, _ in clipped)
            for idx, own in self_times(clipped):
                op_ns[names[idx]] = op_ns.get(names[idx], 0) + own
        busy += union_s(spans)
    chips = len(planes)
    window_s = (hi - lo) / 1e9
    busy_s = busy / chips
    if not 0.0 < busy_s <= window_s:
        raise TraceError(f"busy_s {busy_s} outside (0, window_s {window_s}]")
    op_s = {name: ns / 1e9 / chips for name, ns in op_ns.items()}
    short = {}
    for name, sec in op_s.items():
        short[short_name(name)] = short.get(short_name(name), 0.0) + sec
    ranked = sorted(short.items(), key=lambda kv: -kv[1])
    return {"window_s": window_s, "busy_s": busy_s, "chips": chips,
            "op_s": op_s, "top_ops": [[n, s] for n, s in ranked[:top]]}


_HLO = re.compile(r"^%([A-Za-z_\-]+(?:\.[A-Za-z_\-]+)*)[.\d]* = \(?([a-z0-9]+\[[0-9,]*\])")


def short_name(raw: str) -> str:
    """The trace names a device operation by its whole HLO text. For the
    breakdown: the instruction's name without its number, and its (first)
    result shape, so that the same operation of two programs adds up."""
    m = _HLO.match(raw)
    return f"{m.group(1)} {m.group(2)}" if m else raw[:120]


def time_matching(reduced: dict, pattern: str) -> float:
    """Seconds (per chip) in operations whose name matches `pattern`."""
    rx = re.compile(pattern)
    return sum(s for name, s in reduced["op_s"].items() if rx.search(name))
