#!/usr/bin/env python3
"""dynamo_top: a `top`-style live fleet view for a dynamo_tpu deployment.

Reads only public HTTP surfaces — frontend `/internal/workers` +
`/debug/costs`, each worker's `/worker/stats` (memory + cost + step-
timeline sections) and `/debug/flight?n=` — so it needs no cluster
credentials beyond reach of the frontend. One screen answers: who is
serving what, how full is every KV tier, which tenant is spending the
chips, where each engine's step time goes (per-phase p50/p95 and the
device's idle share by its own end of every program — the bubble the
zero-bubble work must close), and what each engine did in its last few
steps.

Usage:
    python scripts/dynamo_top.py --frontend http://localhost:8000
    python scripts/dynamo_top.py --frontend ... --once          # one frame
    python scripts/dynamo_top.py --frontend ... --plain         # no curses
    python scripts/dynamo_top.py --worker http://localhost:8001 # no frontend

With a frontend, workers are discovered from its registry; `--worker` adds
(or replaces) explicit worker URLs for single-pod/agg setups.
"""

from __future__ import annotations

import argparse
import json
import sys
import time
import urllib.error
import urllib.request
from typing import Any, Dict, List, Optional


def _get(url: str, timeout: float = 3.0) -> Optional[Dict[str, Any]]:
    try:
        with urllib.request.urlopen(url, timeout=timeout) as r:
            return json.loads(r.read().decode())
    except (urllib.error.URLError, OSError, ValueError, TimeoutError):
        return None


def _fmt_bytes(n: float) -> str:
    n = float(n)
    for unit in ("B", "KiB", "MiB", "GiB", "TiB"):
        if abs(n) < 1024.0 or unit == "TiB":
            return f"{n:7.1f}{unit}"
        n /= 1024.0
    return f"{n:.1f}TiB"


def discover_workers(frontend: Optional[str],
                     explicit: List[str]) -> List[str]:
    urls = list(explicit)
    if frontend:
        reg = _get(frontend.rstrip("/") + "/internal/workers")
        for w in (reg or {}).get("workers", []):
            u = w.get("url")
            if u and u not in urls:
                urls.append(u)
    return urls


# ----------------------------------------------------------------- frame --
def collect(frontend: Optional[str], workers: List[str],
            flight_n: int) -> Dict[str, Any]:
    frame: Dict[str, Any] = {"ts": time.strftime("%H:%M:%S"), "workers": []}
    if frontend:
        frame["costs"] = _get(frontend.rstrip("/") + "/debug/costs")
    for url in workers:
        base = url.rstrip("/")
        stats = _get(base + "/worker/stats")
        flight = _get(base + f"/debug/flight?n={flight_n}")
        frame["workers"].append({"url": url, "stats": stats,
                                 "flight": flight})
    return frame


def render(frame: Dict[str, Any], flight_n: int) -> List[str]:
    lines: List[str] = []
    out = lines.append
    out(f"dynamo_top  {frame['ts']}   workers={len(frame['workers'])}")
    out("")

    costs = frame.get("costs")
    if costs and costs.get("tenants"):
        totals = costs.get("totals", {})
        out("TENANT COSTS (fleet)          chip_s        hbm_byte_s")
        for t, c in sorted(costs["tenants"].items(),
                           key=lambda kv: -kv[1].get("chip_seconds", 0)):
            out(f"  {t:<24}{c.get('chip_seconds', 0):>12.3f}"
                f"  {c.get('hbm_byte_seconds', 0):>16.1f}")
        out(f"  {'TOTAL':<24}{totals.get('chip_seconds', 0):>12.3f}"
            f"  {totals.get('hbm_byte_seconds', 0):>16.1f}")
        out("")

    for w in frame["workers"]:
        st = w["stats"]
        if st is None:
            out(f"-- {w['url']}  UNREACHABLE")
            out("")
            continue
        out(f"-- {w['url']}  model={st.get('model')}"
            f"  mode={st.get('disaggregation_mode')}"
            f"  active={st.get('active_seqs')}/{st.get('max_num_seqs')}"
            f"  pending={st.get('pending')}"
            f"  pages={st.get('total_pages', 0) - st.get('free_pages', 0)}"
            f"/{st.get('total_pages')}")
        mem = st.get("memory")
        if mem:
            for tier, owners in mem.get("tiers", {}).items():
                total = sum(owners.values())
                parts = "  ".join(
                    f"{k}={_fmt_bytes(v).strip()}"
                    for k, v in sorted(owners.items(),
                                       key=lambda kv: -kv[1]) if v)
                out(f"   {tier:<6} {_fmt_bytes(total).strip():>10}  {parts}")
            lora = mem.get("lora")
            if lora:
                out(f"   lora   {len(lora.get('resident', []))}"
                    f"/{lora.get('slots_total')} slots resident "
                    f"{sorted(lora.get('resident', []))}")
        wc = st.get("costs")
        if wc and wc.get("tenants"):
            tens = "  ".join(
                f"{t}={c.get('chip_seconds', 0):.2f}s"
                for t, c in sorted(wc["tenants"].items(),
                                   key=lambda kv: -kv[1].get(
                                       "chip_seconds", 0))[:6])
            out(f"   costs  {tens}")
        tl = st.get("timeline")
        if tl and tl.get("steps"):
            dev = tl.get("device") or {}
            bub = tl.get("bubble") or {}
            eater = bub.get("gap_eater")
            # the device's idle share of the thread's time, by its own
            # end of every program; what the host did through most of it;
            # the longest gap a host change could shrink
            idle_by = dev.get("idle_by") or {}
            top = max(idle_by, key=idle_by.get) if idle_by else "-"
            gap = (dev.get("idle_worst") or [{}])[0]
            out(f"   stepln steps={tl.get('steps')}"
                f"  device idle="
                f"{100 * dev.get('idle_s', 0) / (tl.get('loop_wall_s') or 1):.1f}%"
                f" (most in {top})"
                f"  longest gap={gap.get('idle_s', 0) * 1e3:.1f}ms"
                f" before #{gap.get('before_ticket', '-')}"
                f"{('  eater=' + eater) if eater else ''}")
            phases = tl.get("phases") or {}
            if phases:
                parts = "  ".join(
                    f"{n}={p.get('p50_ms', 0):.2f}/"
                    f"{p.get('p95_ms', 0):.2f}ms"
                    f"({p.get('share', 0) * 100:.0f}%)"
                    for n, p in sorted(
                        phases.items(),
                        key=lambda kv: -kv[1].get("total_s", 0)))
                out(f"          p50/p95  {parts}")
            tt = tl.get("token_time") or {}
            if tt.get("gaps"):
                # what a live sequence's tokens waited behind, per token
                per = "  ".join(
                    f"{c}={1e3 * tt['row_s'].get(c, 0) / tt['gaps']:.2f}ms"
                    for c in ("decode", "prompt", "drained"))
                worst = (tt.get("worst") or [{}])[0]
                out(f"          token    {per}"
                    f"  idle={1e3 * dev.get('row_idle_s', 0) / tt['gaps']:.2f}ms"
                    f"  longest gap={tt.get('gap_max_s', 0) * 1e3:.0f}ms"
                    f" ({worst.get('request_id', '-')})")
        fl = w.get("flight")
        if fl and fl.get("records"):
            out(f"   flight ring={fl.get('size')}/{fl.get('capacity')}"
                f"  steps={fl.get('steps_total')}"
                f"  dropped={fl.get('dropped_total')}")
            for rec in fl["records"][-flight_n:]:
                evs = ",".join(e.get("ev", "?")
                               for e in rec.get("events", []))
                phases = " ".join(
                    f"{k}={v:.0f}ms"
                    for k, v in rec.get("phases", {}).items())
                out(f"     #{rec.get('seq')} {rec.get('kind', '-'):<14}"
                    f" act={rec.get('active', 0)}"
                    f" free={rec.get('free_pages', 0)}"
                    f" {phases}{('  [' + evs + ']') if evs else ''}")
        out("")
    return lines


# ------------------------------------------------------------------ main --
def run_plain(args) -> int:
    while True:
        workers = discover_workers(args.frontend, args.worker)
        frame = collect(args.frontend, workers, args.flight)
        sys.stdout.write("\n".join(render(frame, args.flight)) + "\n")
        sys.stdout.flush()
        if args.once:
            return 0
        time.sleep(args.interval)


def run_curses(args) -> int:
    import curses

    def loop(scr):
        curses.use_default_colors()
        scr.timeout(int(args.interval * 1000))
        while True:
            workers = discover_workers(args.frontend, args.worker)
            frame = collect(args.frontend, workers, args.flight)
            scr.erase()
            rows, cols = scr.getmaxyx()
            for i, line in enumerate(render(frame, args.flight)[:rows - 1]):
                scr.addnstr(i, 0, line, cols - 1)
            scr.addnstr(rows - 1, 0, "q to quit", cols - 1)
            scr.refresh()
            if scr.getch() in (ord("q"), 27):
                return 0

    return curses.wrapper(loop)


def main() -> int:
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--frontend", default=None,
                   help="frontend base URL (worker discovery + fleet costs)")
    p.add_argument("--worker", action="append", default=[],
                   help="explicit worker base URL (repeatable)")
    p.add_argument("--interval", type=float, default=2.0,
                   help="refresh interval seconds (default 2)")
    p.add_argument("--flight", type=int, default=5,
                   help="flight-recorder records per worker (default 5)")
    p.add_argument("--once", action="store_true",
                   help="print one frame and exit")
    p.add_argument("--plain", action="store_true",
                   help="plain text output (no curses; implied by --once)")
    args = p.parse_args()
    if not args.frontend and not args.worker:
        p.error("need --frontend and/or --worker")
    if args.once or args.plain or not sys.stdout.isatty():
        return run_plain(args)
    try:
        return run_curses(args)
    except ImportError:
        return run_plain(args)


if __name__ == "__main__":
    raise SystemExit(main())
