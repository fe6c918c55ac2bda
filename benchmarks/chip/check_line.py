#!/usr/bin/env python3
"""Checks the last line of a run against what the driver reads.

The driver's own words (PERF_LEDGER.jsonl, PR 22): the last line is "a JSON
object with the keys correct, attempted, failed, metrics and device, where
metrics gives each metric of this workload as its value and unit, and device
gives platform, kind, count, memory_peak_bytes and, in a traced run, window_s
and busy_s (above 0, at most window_s); other keys are ignored".

    python benchmarks/chip/run.py ... | python benchmarks/chip/check_line.py \
        --workload <cell> --trace <0|1>

Reads standard input (or --file), takes the last non-empty line, prints a
verdict as JSON and exits 1 on any problem. run.py runs the same check on its
own line before it prints it.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

from lib.spec import NAME_RE, UNIT_RE, load_cell  # noqa: E402
from lib.stats import finite  # noqa: E402


def problems_in(line: str, owed: dict, trace: bool) -> list:
    """Everything that the ledger's sentence excludes. `owed` is name ->
    unit of the metrics this run of this cell must print."""
    try:
        obj = json.loads(line, parse_constant=lambda c: float("nan"))
    except ValueError as e:
        return [f"the line is not JSON: {e}"]
    if not isinstance(obj, dict):
        return ["the line is not a JSON object"]
    out = []
    for key in ("correct", "attempted", "failed", "metrics", "device"):
        if key not in obj:
            out.append(f"missing key {key!r}")
    if out:
        return out
    if not isinstance(obj["correct"], bool):
        out.append(f"correct is {obj['correct']!r}, not true or false")
    for key in ("attempted", "failed"):
        v = obj[key]
        if not isinstance(v, int) or isinstance(v, bool) or v < 0:
            out.append(f"{key} is {v!r}, not a count")
    metrics = obj["metrics"]
    if not isinstance(metrics, dict):
        return out + ["metrics is not an object"]
    for name, unit in owed.items():
        if name not in metrics:
            out.append(f"metric {name!r} is owed and missing")
    for name, m in metrics.items():
        if not NAME_RE.match(name):
            out.append(f"metric name {name!r} has a character outside "
                       f"letters, digits, _ . - (or is over 64)")
        if not isinstance(m, dict) or "value" not in m or "unit" not in m:
            out.append(f"metric {name!r} lacks value or unit")
            continue
        if not finite(m["value"]):
            out.append(f"metric {name!r} has the value {m['value']!r}, "
                       f"not a finite number")
        if not isinstance(m["unit"], str) or not UNIT_RE.match(m["unit"]):
            out.append(f"metric {name!r} has the unit {m['unit']!r}: 1 to "
                       f"16 of letters, digits, _ / % . -")
        elif name in owed and m["unit"] != owed[name]:
            out.append(f"metric {name!r} has the unit {m['unit']!r}, "
                       f"BENCHMARK.json says {owed[name]!r}")
        if name not in owed:
            out.append(f"metric {name!r} is not one this run owes")
    device = obj["device"]
    if not isinstance(device, dict):
        return out + ["device is not an object"]
    for key, kind in (("platform", str), ("kind", str), ("count", int),
                      ("memory_peak_bytes", int)):
        if not isinstance(device.get(key), kind) \
                or isinstance(device.get(key), bool):
            out.append(f"device.{key} is {device.get(key)!r}")
    if isinstance(device.get("count"), int) and device["count"] < 1:
        out.append("device.count is under 1")
    if trace:
        w, b = device.get("window_s"), device.get("busy_s")
        if not finite(w) or not finite(b):
            out.append(f"a traced run needs device.window_s and "
                       f"device.busy_s as numbers, got {w!r} and {b!r}")
        elif not 0.0 < b <= w:
            out.append(f"device.busy_s {b} is not above 0 and at most "
                       f"window_s {w}")
    if "breakdown" in obj:
        bd = obj["breakdown"]
        if not trace:
            out.append("breakdown on a run that was not traced")
        if not isinstance(bd, dict):
            out.append("breakdown is not an object")
        else:
            for key, rows in bd.items():
                if key not in ("device_ops", "idle_gaps"):
                    out.append(f"breakdown has the key {key!r}")
                elif (not isinstance(rows, list) or len(rows) > 10 or any(
                        not (isinstance(r, list) and len(r) == 2
                             and isinstance(r[0], str) and finite(r[1]))
                        for r in rows)):
                    out.append(f"breakdown.{key} is not at most 10 pairs "
                               f"of [name, seconds]")
    return out


def last_line(text: str) -> str:
    lines = [ln for ln in text.splitlines() if ln.strip()]
    return lines[-1] if lines else ""


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--trace", type=int, choices=(0, 1), required=True)
    p.add_argument("--file", help="read this file, not standard input")
    args = p.parse_args(argv)
    if args.file:
        with open(args.file) as f:
            text = f.read()
    else:
        text = sys.stdin.read()
    line = last_line(text)
    cell = load_cell(args.workload)
    found = problems_in(line, cell.owed(bool(args.trace)), bool(args.trace))
    print(json.dumps({"workload": args.workload, "trace": args.trace,
                      "ok": not found, "problems": found}))
    return 1 if found else 0


if __name__ == "__main__":
    sys.exit(main())
