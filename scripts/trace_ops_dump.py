#!/usr/bin/env python3
"""By hand, after `run.py ... --trace 1 --keep-trace`: every device
operation of the kept trace with its own seconds in the slice, longest first
(the names layer_metrics/*.json patterns are read off), and how many times
each ran in the trace (an operation that runs once a step counts the steps).

    python scripts/trace_ops_dump.py <out.json> [work dir]
"""
import collections
import json
import os
import sys

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(HERE, "benchmarks", "chip"))
from lib import trace_reduce  # noqa: E402
from lib.spec import load_device  # noqa: E402

out = sys.argv[1]
work = sys.argv[2] if len(sys.argv) > 2 else os.path.join(
    HERE, "chiprun_out", "bench", "trace")
with open(os.path.join(work, "events.json")) as f:
    trace = json.load(f)
dev = load_device("TPU v5 lite")
red = trace_reduce.reduce(trace, dev["trace_plane"], dev["trace_ops_line"],
                          top=40)
ops = sorted(red["op_s"].items(), key=lambda kv: -kv[1])
# how many times each ran inside the slice reduce() took (its margins)
lines = [line for _, lines in trace_reduce._device_lines(
    trace, dev["trace_plane"], dev["trace_ops_line"]) for line in lines]
lo = min(ev[0] for line in lines for ev in line) + 0.25e9
hi = max(ev[0] + ev[1] for line in lines for ev in line) - 0.25e9
ran = collections.Counter(trace["names"][ev[2]] for line in lines
                          for ev in line if lo <= ev[0] < hi)
with open(out, "w") as f:
    json.dump({"window_s": red["window_s"], "busy_s": red["busy_s"],
               "ops": [[round(s, 6), name[:400], ran[name]]
                       for name, s in ops[:400]]},
              f, indent=0)
print(red["window_s"], red["busy_s"], len(ops))
