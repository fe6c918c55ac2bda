#!/usr/bin/env python3
"""The builder's measurement of a cell, as the contract sets bounds from it:
`--sets` sets of `--runs` runs of the driver's exact command, the same seeds
in every set, each run a process of its own; then `--traced` traced runs.
Every last line goes through check_line.py. Lines, verdicts and the spread of
each metric ((Q3 - Q1) / median with statistics.quantiles(n=4), per set) are
written to chiprun_out/bench/records/<cell>.json; keep them in
benchmarks/chip/records/.

    python benchmarks/chip/measure_sets.py --workload qwen7b-chat-r80 \
        --seconds 30 --runs 6 --sets 2 --traced 1
"""

from __future__ import annotations

import argparse
import json
import os
import statistics
import subprocess
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

from check_line import last_line, problems_in  # noqa: E402
from lib.spec import REPO, load_cell  # noqa: E402
from lib.stats import quartile_spread  # noqa: E402

SEED_BASE = 2147483700   # above 31 bits, as the driver's seeds are


def one_run(command: list, cell, seed: int, seconds: int, trace: int) -> dict:
    argv = command + ["--workload", cell.name, "--seed", str(seed),
                      "--seconds", str(seconds), "--trace", str(trace)]
    t0 = time.monotonic()
    done = subprocess.run(argv, cwd=REPO, capture_output=True, text=True)
    wall = time.monotonic() - t0
    line = last_line(done.stdout)
    lines = [ln for ln in done.stdout.splitlines() if ln.strip()]
    found = (problems_in(line, cell.owed(bool(trace)), bool(trace))
             if done.returncode == 0 else
             [f"exit code {done.returncode}: {done.stderr[-1500:]}"])
    rec = {"argv": argv, "seed": seed, "trace": trace, "rc": done.returncode,
           "wall_s": wall, "check_line": {"ok": not found,
                                          "problems": found},
           "line": json.loads(line) if not found else line,
           "info": (json.loads(lines[-2]) if not found and len(lines) > 1
                    else None)}
    print(json.dumps({k: rec[k] for k in
                      ("seed", "trace", "rc", "wall_s", "check_line")}),
          file=sys.stderr, flush=True)
    if found:
        print(done.stderr[-3000:], file=sys.stderr, flush=True)
    return rec


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seconds", type=int, default=None,
                   help="default: BENCHMARK.json's run_seconds")
    p.add_argument("--runs", type=int, default=6)
    p.add_argument("--sets", type=int, default=2)
    p.add_argument("--traced", type=int, default=1)
    p.add_argument("--tag", default="")
    args = p.parse_args(argv)
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        bench = json.load(f)
    seconds = args.seconds or bench["run_seconds"]
    cell = load_cell(args.workload)
    out = {"workload": args.workload, "seconds": seconds,
           "command": bench["command"], "sets": [], "traced": []}
    for s in range(args.sets):
        out["sets"].append([
            one_run(bench["command"], cell, SEED_BASE + i, seconds, 0)
            for i in range(args.runs)])
    for i in range(args.traced):
        out["traced"].append(
            one_run(bench["command"], cell, SEED_BASE + i, seconds, 1))
    summary = {}
    for name in cell.owed(False):
        per_set = []
        for runs in out["sets"]:
            vals = [r["line"]["metrics"][name]["value"] for r in runs
                    if r["check_line"]["ok"]]
            if name == "setup_s":
                vals = vals[1:] if runs is out["sets"][0] else vals
            per_set.append({
                "values": vals,
                "median": statistics.median(vals) if vals else None,
                "spread": quartile_spread(vals) if len(vals) >= 2 else None})
        spreads = [s["spread"] for s in per_set if s["spread"] is not None]
        summary[name] = {"sets": per_set,
                         "widest_spread": max(spreads) if spreads else None}
    out["summary"] = summary
    out["all_ok"] = all(r["check_line"]["ok"] and r["line"]["correct"]
                        for runs in out["sets"] + [out["traced"]]
                        for r in runs)
    out_dir = os.path.join(REPO, "chiprun_out", "bench", "records")
    os.makedirs(out_dir, exist_ok=True)
    path = os.path.join(out_dir, f"{args.workload}{args.tag}.json")
    with open(path, "w") as f:
        json.dump(out, f, indent=1)
    print(json.dumps({"workload": args.workload, "all_ok": out["all_ok"],
                      "summary": {k: {"medians": [s["median"] for s in
                                                  v["sets"]],
                                      "widest_spread": v["widest_spread"]}
                                  for k, v in summary.items()},
                      "traced": [r["line"] for r in out["traced"]]}))
    return 0 if out["all_ok"] else 1


if __name__ == "__main__":
    sys.exit(main())
