#!/usr/bin/env python3
"""By hand, after `run.py ... --trace 1 --keep-trace`: every device
operation of the kept trace with its own seconds in the slice, longest first
(the names layer_metrics/*.json patterns are read off).

    python scripts/trace_ops_dump.py <out.json> [work dir]
"""
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
with open(out, "w") as f:
    json.dump({"window_s": red["window_s"], "busy_s": red["busy_s"],
               "ops": [[round(s, 6), name[:400]] for name, s in ops[:400]]},
              f, indent=0)
print(red["window_s"], red["busy_s"], len(ops))
