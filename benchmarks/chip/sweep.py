#!/usr/bin/env python3
"""Finds the knee of an open-loop cell once, on the chip: the highest rate of
a ladder at which at least the mix's `share_within` of the requests sent meet
both of its limits (TTFT, token time; a failed request misses) and the
backlog does not grow. One server, every rate against it in turn.

    python benchmarks/chip/sweep.py --workload qwen7b-chat-r80 \
        --rates 4,5.5,7.5,10,13 --seconds 20 --seed 11 [--write-cell]

Prints the table as JSON and writes it to chiprun_out/bench/sweep/<cell>.json
(keep it in benchmarks/chip/records/). --write-cell puts 0.8 x knee, rounded
down to 0.5, into cells/<cell>.json as `load.rate_rps`: the benchmark itself
never searches for a rate.
"""

from __future__ import annotations

import argparse
import json
import math
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

from lib import stats as st  # noqa: E402
from lib.server import Server, ServerFailure, device_of, log  # noqa: E402
from lib.spec import CHIP_DIR, REPO, SpecError, load_cell  # noqa: E402
from lib.traffic import Sender, run_open  # noqa: E402


def in_flight(requests, t: float) -> int:
    return sum(1 for r in requests if r.sent is not None and r.sent <= t
               and (r.done is None or r.done > t))


def one_rate(srv, cell, mix, rate: float, seconds: float, seed: int) -> dict:
    lead_in_s = float(mix["lead_in_s"])
    sender = Sender("127.0.0.1", srv.fport, srv.model_name, mix,
                    cell.config["chat_template_overhead_tokens"], seed,
                    time.monotonic() + lead_in_s + 0.2)
    requests = run_open(sender, mix, rate, lead_in_s, seconds)
    win = [r for r in requests if r.phase == "window"]
    lim = mix["limits"]
    fail_s = float(mix["request_timeout_s"])
    within = 0
    for r in win:
        tpot = r.tpot_s(fail_s)
        if (r.ttft_s(fail_s) * 1e3 <= lim["ttft_ms"]
                and (tpot is None or tpot * 1e3 <= lim["tpot_ms"])):
            within += 1
    mid, end = in_flight(requests, seconds / 2), in_flight(requests, seconds)
    e2e = st.end_to_end(requests, seconds, 0.0, fail_s)
    e2e.pop("setup_s")
    row = {"rate_rps": rate, "sent": len(win),
           "failed": sum(1 for r in win if not r.ok),
           "share_within": within / len(win),
           "in_flight_mid": mid, "in_flight_end": end,
           # a queue that is longer at the end than in the middle by more
           # than a quarter (and a few requests) is growing
           "backlog_grows": end > 1.25 * mid + 4,
           **e2e, "generator_lateness": st.lateness(requests)}
    row["sustained"] = (row["share_within"] >= lim["share_within"]
                        and not row["backlog_grows"] and not row["failed"])
    return row


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--rates", required=True,
                   help="comma-separated requests per second, ascending")
    p.add_argument("--seconds", type=float, default=20.0)
    p.add_argument("--seed", type=int, default=11)
    p.add_argument("--variant", default=None)
    p.add_argument("--write-cell", action="store_true")
    args = p.parse_args(argv)
    rates = [float(x) for x in args.rates.split(",")]
    try:
        cell = load_cell(args.workload)
        vspec = cell.config["variants"][args.variant] if args.variant else {}
        mix = {**cell.traffic, **vspec.get("traffic", {})}
        if mix["loop"] != "open":
            raise SpecError(f"cell {args.workload!r} is not an open loop")
        t0 = time.monotonic()
        rows = []
        with Server(cell.config, cell.model_dir, args.seed,
                    args.variant) as srv:
            srv.start()
            srv.wait_ready()
            setup_s = time.monotonic() - t0
            warm = srv.stats()
            log(f"ready after {setup_s:.1f}s on {warm['device_kind']}")
            for rate in rates:
                row = one_rate(srv, cell, mix, rate, args.seconds, args.seed)
                log(json.dumps(row))
                rows.append(row)
                if row["backlog_grows"] and row["share_within"] < 0.5:
                    break   # far past the knee: higher rates teach nothing
            after = srv.stats()
            fallbacks = srv.fallbacks()
            srv.stop()
    except (ServerFailure, SpecError) as e:
        print(f"benchmarks/chip/sweep.py: FAILED: {e}", file=sys.stderr)
        return 1
    sustained = [r["rate_rps"] for r in rows if r["sustained"]]
    knee = max(sustained) if sustained else None
    table = {
        "workload": args.workload, "variant": args.variant,
        "seconds": args.seconds, "seed": args.seed,
        "limits": mix["limits"], "rows": rows, "knee_rps": knee,
        "rate_rps": (math.floor(0.8 * knee * 2) / 2) if knee else None,
        "device": device_of(after),
        "setup_s": setup_s, "warmup": warm["warmup"],
        "compiled_programs": [warm["compiled_programs"],
                              after["compiled_programs"]],
        "fallbacks": [[op, why, n] for (op, why), n
                      in sorted(fallbacks.items())],
        "health": after["health"]["state"],
    }
    out_dir = os.path.join(REPO, "chiprun_out", "bench", "sweep")
    os.makedirs(out_dir, exist_ok=True)
    with open(os.path.join(out_dir, args.workload + ".json"), "w") as f:
        json.dump(table, f, indent=1)
    if args.write_cell:
        if table["rate_rps"] is None:
            print("benchmarks/chip/sweep.py: no rate of the ladder was "
                  "sustained; the cell file is left as it was",
                  file=sys.stderr)
            print(json.dumps(table))
            return 1
        path = os.path.join(CHIP_DIR, "cells", args.workload + ".json")
        with open(path) as f:
            cell_file = json.load(f)
        cell_file["load"]["rate_rps"] = table["rate_rps"]
        cell_file["rate_from"] = (
            f"0.8 x the knee of {knee} req/s, rounded down to 0.5 "
            f"(sweep on {after['device_kind']}, rates {args.rates}, "
            f"{args.seconds:.0f} s each, seed {args.seed}; table in "
            f"records/sweep-{args.workload}.json)")
        with open(path, "w") as f:
            json.dump(cell_file, f, indent=2)
            f.write("\n")
        os.makedirs(os.path.join(out_dir, "cells"), exist_ok=True)
        with open(os.path.join(out_dir, "cells", args.workload + ".json"),
                  "w") as f:
            json.dump(cell_file, f, indent=2)
            f.write("\n")
    print(json.dumps(table))
    return 0


if __name__ == "__main__":
    sys.exit(main())
