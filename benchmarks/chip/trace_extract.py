#!/usr/bin/env python3
"""Child of run.py: reads the profiler's .xplane.pb with jax.profiler
.ProfileData and writes the device planes' events as plain JSON for
lib/trace_reduce.py. Runs with JAX_PLATFORMS=cpu after the worker has
exited, so that the parent stays off JAX and the chip is free.

    python benchmarks/chip/trace_extract.py <trace dir> <plane regex> <out.json>
"""

from __future__ import annotations

import glob
import json
import os
import re
import sys


def extract(trace_dir: str, plane_re: str) -> dict:
    from jax.profiler import ProfileData

    paths = sorted(glob.glob(os.path.join(
        trace_dir, "**", "*.xplane.pb"), recursive=True))
    if not paths:
        raise SystemExit(f"no .xplane.pb under {trace_dir}")
    names, index, planes, seen = [], {}, [], []
    for path in paths:
        for plane in ProfileData.from_file(path).planes:
            seen.append(plane.name)
            if not re.search(plane_re, plane.name):
                continue
            lines = []
            for line in plane.lines:
                events = []
                for ev in line.events:
                    idx = index.get(ev.name)
                    if idx is None:
                        idx = index[ev.name] = len(names)
                        names.append(ev.name)
                    events.append([int(ev.start_ns), int(ev.duration_ns),
                                   idx])
                lines.append({"name": line.name, "events": events})
            planes.append({"name": plane.name, "lines": lines})
    return {"names": names, "planes": planes, "planes_seen": seen}


def main(argv) -> int:
    trace_dir, plane_re, out = argv
    trace = extract(trace_dir, plane_re)
    with open(out, "w") as f:
        json.dump(trace, f)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
