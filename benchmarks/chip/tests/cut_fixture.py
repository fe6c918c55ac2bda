#!/usr/bin/env python3
"""Cuts the small recorded trace that the reducer's tests run on out of one
real trace, as trace_extract.py wrote it (run.py --keep-trace leaves it in
chiprun_out/bench/trace/events.json): the first `n` events of every line of
the first device plane, with the names they use, and a digest of the whole
trace for reading by hand (which line holds the operations, how the kernels
are named).

    python benchmarks/chip/tests/cut_fixture.py <events.json> <out dir> [n]
"""

import collections
import json
import os
import sys


def main(argv) -> int:
    src, out_dir = argv[0], argv[1]
    n = int(argv[2]) if len(argv) > 2 else 250
    with open(src) as f:
        trace = json.load(f)
    names = trace["names"]
    digest = {"planes_seen": trace["planes_seen"], "planes": []}
    for plane in trace["planes"]:
        lines = []
        for line in plane["lines"]:
            total = collections.Counter()
            for _, dur, idx in line["events"]:
                total[names[idx]] += dur
            ev = line["events"]
            lines.append({
                "name": line["name"], "events": len(ev),
                "first_ns": min((e[0] for e in ev), default=None),
                "last_ns": max((e[0] + e[1] for e in ev), default=None),
                "top_by_time_s": [[k, v / 1e9] for k, v
                                  in total.most_common(40)]})
        digest["planes"].append({"name": plane["name"], "lines": lines})
    os.makedirs(out_dir, exist_ok=True)
    with open(os.path.join(out_dir, "trace_digest.json"), "w") as f:
        json.dump(digest, f, indent=1)
    if trace["planes"]:
        plane = trace["planes"][0]
        used, lines = {}, []
        for line in plane["lines"]:
            events = []
            for start, dur, idx in sorted(line["events"])[:n]:
                events.append([start, dur, used.setdefault(idx, len(used))])
            lines.append({"name": line["name"], "events": events})
        small = {"names": [names[i] for i in used],
                 "planes": [{"name": plane["name"], "lines": lines}],
                 "planes_seen": trace["planes_seen"]}
        with open(os.path.join(out_dir, "small_trace.json"), "w") as f:
            json.dump(small, f)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
