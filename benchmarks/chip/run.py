#!/usr/bin/env python3
"""One run of one cell of the benchmark, over the served path.

    python benchmarks/chip/run.py --workload <cell> --seed <n> \
        --seconds <s> --trace <0|1>

Starts the cell's frontend and worker as child processes (this parent never
imports JAX), waits until the worker is ready and registered (that is
`setup_s`, from this process's start), sends a greedy probe, an unmeasured
lead-in of the cell's own traffic, measures arrivals for --seconds, lets what
arrived finish, probes again, reads the worker's /worker/stats and /metrics,
stops both children with SIGTERM, and prints ONE JSON object as its last
line: correct, attempted, failed, metrics, device, and with --trace 1
breakdown. With --trace 0 the metrics are the cell's end-to-end metrics; with
--trace 1 its per-layer metrics, read from the client's records, from
/worker/stats polled at 1 Hz, and from a profiler trace of a few seconds
taken by the worker (GET /debug/trace) in the middle of the window.

A metric that is owed and cannot be computed, a child that dies, a missing
accelerator: exit 1 and no result line. `--variant cpu` rehearses the whole
command on the CPU at a tiny preset and `--variant small` on the chip with a
small model; both always print `correct: false`.
"""

from __future__ import annotations

import argparse
import dataclasses
import io
import json
import os
import shutil
import subprocess
import sys
import threading
import time
import zipfile

T_START = time.monotonic()

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

from check_line import problems_in  # noqa: E402
from lib import stats as st  # noqa: E402
from lib import trace_reduce  # noqa: E402
from lib.server import Server, ServerFailure, device_of, get, log  # noqa: E402
from lib.spec import CHIP_DIR, REPO, SpecError, load_cell, load_device  # noqa: E402
from lib.traffic import Sender, run_mix  # noqa: E402

TRACE_S = 3.0            # the profiler's slice: hundreds of steps at ~12 ms
WORK_DIR = os.path.join(REPO, "chiprun_out", "bench", "trace")


class RunFailure(Exception):
    """The run cannot give a result line."""


@dataclasses.dataclass
class Context:
    """What a per-layer reader may read."""
    requests: list        # lib.stats.Request, lead-in and window
    window_s: float
    snapshots: list       # [(t, /worker/stats)] at 1 Hz over the window
    trace: dict | None    # lib.trace_reduce.reduce()'s result
    fail_s: float         # what a failed request reads as, in every tail


def probe(sender: Sender, config: dict) -> st.Request:
    """The fixed greedy request: a raw completion short enough to fill no
    KV page, so that the prefix cache cannot change its path."""
    p = config["probe"]
    req = st.Request(-1, "probe", sender.now(), 0, p["max_tokens"])
    body = json.dumps({"model": sender.model, "prompt": p["prompt"],
                       "max_tokens": p["max_tokens"], "stream": True,
                       "stream_options": {"include_usage": True},
                       **sender.mix["request"]}).encode()
    for _ in range(10):
        sender.send(req, keep_text=True, path="/v1/completions", body=body)
        if req.status != 503:
            break
        # the frontend lost the worker's heartbeat under load (seen once on
        # the chip, PR 23); the next beat, a second later, lists it again.
        # The probe is not measured, so it may wait for that.
        log(f"probe refused ({req.error}); trying again")
        req.error, req.frame_times, req.first = "", [], None
        time.sleep(1.0)
    if not req.ok:
        raise RunFailure(f"the probe request failed: {req.error}")
    return req


def capture_trace(srv: Server, sender: Sender, seconds: float, out: dict):
    """Thread: asks the WORKER (only the process that holds the chip can
    trace it) for a slice in the middle of the window."""
    start = max(0.0, (seconds - TRACE_S) / 2.0)
    while sender.now() < start:
        time.sleep(0.05)
    try:
        out["t_start"] = sender.now()
        out["zip"] = get(f"{srv.worker_url}/debug/trace?duration_s={TRACE_S}",
                         timeout=180.0)
        out["t_end"] = sender.now()
    except OSError as e:
        out["error"] = f"{type(e).__name__}: {e}"


def poll_stats(srv: Server, sender: Sender, seconds: float, out: list):
    """Thread: /worker/stats at t = 0, 1, 2, ... and at the window's end."""
    ticks = [float(i) for i in range(int(seconds))] + [seconds]
    for t in ticks:
        while sender.now() < t:
            time.sleep(0.02)
        try:
            out.append((sender.now(), srv.stats()))
        except (OSError, ValueError) as e:
            log(f"stats poll at t={t} failed: {e}")


def reduce_trace(zipped: bytes, device: dict, keep: bool) -> dict:
    shutil.rmtree(WORK_DIR, ignore_errors=True)
    os.makedirs(WORK_DIR)
    try:
        with zipfile.ZipFile(io.BytesIO(zipped)) as z:
            z.extractall(WORK_DIR)
        events = os.path.join(WORK_DIR, "events.json")
        env = dict(os.environ, JAX_PLATFORMS="cpu")
        env.pop("XLA_FLAGS", None)
        done = subprocess.run(
            [sys.executable, os.path.join(CHIP_DIR, "trace_extract.py"),
             WORK_DIR, device["trace_plane"], events],
            env=env, capture_output=True, text=True, timeout=600)
        if done.returncode != 0:
            raise RunFailure(f"trace_extract.py failed:\n{done.stderr[-2000:]}")
        with open(events) as f:
            trace = json.load(f)
        try:
            return trace_reduce.reduce(trace, device["trace_plane"],
                                       device["trace_ops_line"])
        except trace_reduce.TraceError as e:
            lines = {p["name"]: [(ln["name"], len(ln["events"]))
                                 for ln in p["lines"]]
                     for p in trace["planes"]}
            raise RunFailure(f"{e}\nplanes seen: {trace['planes_seen']}\n"
                             f"lines of the device planes: {lines}") from e
    finally:
        if not keep:
            shutil.rmtree(WORK_DIR, ignore_errors=True)


def judge(variant, expected_fallbacks, warm, after, fallbacks, requests,
          probes, stop_problems) -> list:
    """Why `correct` is false; empty when it is true."""
    why = []
    if variant:
        why.append(f"--variant {variant} is a rehearsal")
    if after["platform"] != "tpu":
        why.append(f"platform is {after['platform']!r}")
    done = [r for r in requests if r.ok]
    inexact = [r for r in done if not r.exact]
    if inexact:
        r = inexact[0]
        why.append(
            f"{len(inexact)} of {len(done)} responses do not carry exactly "
            f"the tokens asked for (request {r.idx}: prompt "
            f"{r.prompt_tokens}/{r.want_prompt}, completion "
            f"{r.completion_tokens}/{r.want_out})")
    if probes[0].text != probes[1].text or not probes[0].text:
        why.append(f"the greedy probe gave {probes[0].text!r} before the "
                   f"window and {probes[1].text!r} after it")
    if any(p.completion_tokens != p.want_out for p in probes):
        why.append("the probe did not return the tokens asked for")
    if after["compiled_programs"] != warm["compiled_programs"]:
        why.append(f"compiled in the window: {warm['compiled_programs']} "
                   f"programs at /ready, {after['compiled_programs']} after")
    health = after["health"]
    if (health["state"] != "healthy" or health["trips_total"]
            or health["integrity_faults_total"]):
        why.append(f"watchdog not clean: {health}")
    unexpected = sorted(set(fallbacks)
                        - {tuple(f) for f in expected_fallbacks})
    if unexpected:
        why.append(f"unexpected Pallas->XLA fallbacks: {unexpected}")
    return why + stop_problems


def run(args) -> tuple:
    """(the result line, name -> unit of the metrics it owes)."""
    cell = load_cell(args.workload)
    variant = args.variant
    if variant and variant not in cell.config.get("variants", {}):
        raise SpecError(f"configuration {cell.config['name']!r} has no "
                        f"variant {variant!r}")
    vspec = cell.config["variants"][variant] if variant else {}
    mix = {**cell.traffic, **vspec.get("traffic", {})}
    load = vspec.get("load", cell.load)
    trace = bool(args.trace)

    with Server(cell.config, cell.model_dir, args.seed, variant) as srv:
        srv.start()
        srv.wait_ready()
        setup_s = time.monotonic() - T_START
        warm = srv.stats()
        log(f"ready after {setup_s:.1f}s on {warm['device_count']} x "
            f"{warm['device_kind']} ({warm['platform']}); warmup "
            f"{warm['warmup']}")
        want = vspec.get("platform", "tpu")
        if warm["platform"] != want:
            raise RunFailure(f"the worker runs on {warm['platform']!r}, "
                             f"this run is for {want!r}")
        if warm["device_count"] < cell.chips:
            raise RunFailure(f"{warm['device_count']} device(s), the cell "
                             f"asks for {cell.chips}")
        device = load_device(warm["device_kind"])

        lead_in_s = float(mix["lead_in_s"])
        probe_sender = Sender("127.0.0.1", srv.fport, srv.model_name, mix,
                              0, args.seed, time.monotonic())
        probes = [probe(probe_sender, cell.config)]
        sender = Sender("127.0.0.1", srv.fport, srv.model_name, mix,
                        cell.config["chat_template_overhead_tokens"],
                        args.seed, time.monotonic() + lead_in_s + 0.2)
        snapshots, shot, side = [], {}, []
        if trace:
            side = [threading.Thread(target=poll_stats, daemon=True, args=(
                        srv, sender, args.seconds, snapshots)),
                    threading.Thread(target=capture_trace, daemon=True, args=(
                        srv, sender, args.seconds, shot))]
            for th in side:
                th.start()
        requests = run_mix(sender, mix, load, args.seconds)
        for th in side:
            th.join(timeout=240.0)
        probes.append(probe(probe_sender, cell.config))
        after = srv.stats()
        fallbacks = srv.fallbacks()
        if not srv.worker.alive():
            raise RunFailure(f"the worker died:\n{srv.worker.tail()}")
        stop_problems = srv.stop()

    reduced = None
    if trace:
        if "zip" not in shot:
            raise RunFailure(f"no trace came back from /debug/trace: "
                             f"{shot.get('error', 'the capture hung')}")
        reduced = reduce_trace(shot["zip"], device, args.keep_trace)

    fail_s = float(mix["request_timeout_s"])
    window = [r for r in requests if r.phase == "window"]
    failed = [r for r in window if not r.ok]
    why = judge(variant, (vspec or cell.config)["expected_fallbacks"], warm,
                after, fallbacks, requests, probes, stop_problems)
    e2e = st.end_to_end(requests, args.seconds, setup_s, fail_s)
    ctx = Context(requests, args.seconds, snapshots, reduced, fail_s)
    owed = cell.owed(trace)
    values = {}
    if trace:
        for m in cell.per_layer:
            args_m = {**m.args, **vspec.get("metric_args", {}).get(m.name, {})}
            values[m.name] = m.reader.read(ctx, args_m)
    else:
        values = {name: e2e.get(name) for name in owed}
    missing = [n for n in owed if not st.finite(values.get(n))]
    info = {
        "info": args.workload, "seed": args.seed, "seconds": args.seconds,
        "trace": args.trace, "variant": variant, "load": load,
        "end_to_end": e2e, "generator_lateness": st.lateness(requests),
        "requests": {"lead_in": len(requests) - len(window),
                     "window": len(window), "failed": len(failed),
                     "errors": sorted({r.error for r in failed})[:5]},
        "not_correct_because": why,
        "setup": {"setup_s": setup_s, "warmup": warm["warmup"],
                  "compiled_programs": warm["compiled_programs"]},
        "attention_traced": after["attention"]["traced"],
        "fallbacks": [[op, reason, n] for (op, reason), n
                      in sorted(fallbacks.items())],
        "probe": probes[0].text,
        "trace_capture": {k: v for k, v in shot.items() if k != "zip"},
        "trace_top_ops": reduced["top_ops"] if reduced else None,
    }
    print(json.dumps(info), flush=True)
    if missing:
        raise RunFailure(f"owed metrics without a finite value: {missing} "
                         f"(values: { {n: values.get(n) for n in missing} })")

    dev = device_of(after)
    line = {"correct": not why, "attempted": len(window),
            "failed": len(failed),
            "metrics": {n: {"value": values[n], "unit": u}
                        for n, u in owed.items()},
            "device": dev}
    if trace:
        dev["window_s"] = reduced["window_s"]
        dev["busy_s"] = reduced["busy_s"]
        line["breakdown"] = {"device_ops": reduced["top_ops"]}
    return line, owed


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--trace", type=int, choices=(0, 1), required=True)
    p.add_argument("--variant", default=None,
                   help="a rehearsal named in the configuration's "
                        "`variants` (cpu, small); never `correct`")
    p.add_argument("--keep-trace", action="store_true",
                   help="leave the unpacked trace and its events in "
                        "chiprun_out/bench/trace/")
    args = p.parse_args(argv)
    try:
        line, owed = run(args)
        text = json.dumps(line)
        found = problems_in(text, owed, bool(args.trace))
        if found:
            raise RunFailure(f"the result line would be refused: {found}")
    except (RunFailure, ServerFailure, SpecError) as e:
        print(f"benchmarks/chip/run.py: FAILED: {e}", file=sys.stderr,
              flush=True)
        return 1
    finally:
        assert "jax" not in sys.modules, \
            "the benchmark's parent imported jax; it must leave the chip alone"
    print(text, flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
