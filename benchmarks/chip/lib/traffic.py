"""One general traffic generator, driven by a mix file (traffic/<mix>.json).

A mix fixes the loop (open: arrivals on a schedule whatever the server
does; closed: each client sends its next request when the last completes),
the length distributions, sharing and the lead-in. The cell adds the rate or
the client count. From `--seed` come the prompts' bytes (and, in the worker,
the weights), never the schedule: every seed sends the same stratified sample
of each distribution at the same due times in the same (shuffled) order. A
tail under queueing depends on which request follows which: on the chip, runs
that differed only in the ORDER of one set of sizes differed by up to 14% in
`tpot_p95_ms`, where two runs of one order differed by under 3% (PERF.md,
PR 23).

A repaired copy of benchmarks/utils/loadgen.py's `run_one`/`run_open_loop`:
requests are timed from when they were DUE, lengths are drawn, and the
schedule is the benchmark's own.
"""

from __future__ import annotations

import http.client
import json
import math
import random
import statistics
import threading
import time

from .stats import Request

_WORDS = ("tensor", "page", "window", "chunk", "decode", "prefill", "router",
          "mesh", "cache", "token", "batch", "shard", "kernel", "stream")


# ------------------------------------------------------------- the plan --


def stratified(dist: dict, n: int) -> list:
    """n values at the quantiles (i + 0.5) / n of `dist`, clipped to its
    min and max: the same multiset for every seed."""
    if n <= 0:
        return []
    us = [(i + 0.5) / n for i in range(n)]
    kind = dist["dist"]
    if kind == "lognormal":
        nd = statistics.NormalDist()
        raw = [dist["median"] * math.exp(dist["sigma"] * nd.inv_cdf(u))
               for u in us]
    elif kind == "fixed":
        raw = [dist["value"]] * n
    elif kind == "uniform":
        raw = [dist["min"] + u * (dist["max"] - dist["min"]) for u in us]
    else:
        raise ValueError(f"unknown length distribution {kind!r}")
    return [int(min(dist["max"], max(dist["min"], round(v)))) for v in raw]


def gaps(arrivals: str, n: int, duration_s: float) -> list:
    """n inter-arrival gaps that sum to duration_s: exponential quantiles
    for a Poisson process, equal gaps for a uniform one."""
    if n <= 0:
        return []
    if arrivals == "poisson":
        raw = [-math.log(1.0 - (i + 0.5) / n) for i in range(n)]
    elif arrivals == "uniform":
        raw = [1.0] * n
    else:
        raise ValueError(f"unknown arrival process {arrivals!r}")
    scale = duration_s / sum(raw)
    return [g * scale for g in raw]


def _ordered(values: list, salt: str) -> list:
    """The mix's own shuffle of `values`: the same for every seed."""
    out = list(values)
    random.Random(f"mix:{salt}").shuffle(out)
    return out


def lengths(mix: dict, n: int, salt: str) -> list:
    """[(prompt_tokens, output_tokens)] x n, each list shuffled apart; a
    pair that would overrun `max_total_tokens` gives up prompt tokens."""
    prompts = _ordered(stratified(mix["prompt_tokens"], n), salt + ":prompt")
    outs = _ordered(stratified(mix["output_tokens"], n), salt + ":out")
    cap = mix["max_total_tokens"]
    floor = mix["prompt_tokens"]["min"]
    return [(max(floor, min(p, cap - o)), o) for p, o in zip(prompts, outs)]


def arrival_times(mix: dict, rate_rps: float, duration_s: float,
                  salt: str) -> list:
    """Due times in [0, duration_s) of round(rate * duration / burst)
    groups of `burst` simultaneous arrivals."""
    burst = int(mix.get("burst_size", 1))
    groups = int(round(rate_rps * duration_s / burst))
    g = _ordered(gaps(mix["arrivals"], groups, duration_s), salt + ":gaps")
    times, t = [], 0.0
    for gap in g:
        times.extend([t] * burst)
        t += gap
    return times


def prompt_text(n_bytes: int, seed: int, idx: int, shared: str) -> str:
    """ASCII of exactly n_bytes: `shared` (the mix's shared prefix, if any),
    then a tag unique to (idx, seed), the index first so that two prompts
    differ within the first KV page (template + BOS leave it 6 bytes of
    content) and share no page they were not meant to, then filler words."""
    rng = random.Random(f"{seed}:text:{idx}")
    parts = [shared, f"{idx:04x}.{seed:x}|"]
    size = len(shared) + len(parts[1])
    while size < n_bytes:
        w = rng.choice(_WORDS)
        parts.append(" " + w)
        size += len(w) + 1
    return "".join(parts)[:n_bytes].ljust(n_bytes, ".")


def shared_prefix(mix: dict) -> str:
    n = int(mix.get("shared_prefix_tokens", 0))
    return prompt_text(n, 0, 0, "") if n else ""


# ------------------------------------------------------------ a request --


class Sender:
    """Sends one streamed chat request to the frontend and stamps every
    frame on the client's clock."""

    def __init__(self, host: str, port: int, model: str, mix: dict,
                 overhead_tokens: int, seed: int, t_zero: float):
        self.host, self.port, self.model = host, port, model
        self.mix = mix
        self.overhead = overhead_tokens
        self.seed = seed
        self.t_zero = t_zero          # monotonic time of the window's start
        self.shared = shared_prefix(mix)
        self.timeout_s = float(mix["request_timeout_s"])

    def now(self) -> float:
        return time.monotonic() - self.t_zero

    def body(self, req: Request) -> bytes:
        content = prompt_text(req.want_prompt - self.overhead, self.seed,
                              req.idx, self.shared)
        payload = {"model": self.model,
                   "messages": [{"role": "user", "content": content}],
                   "max_tokens": req.want_out, "stream": True,
                   "stream_options": {"include_usage": True},
                   **self.mix["request"]}
        return json.dumps(payload).encode()

    def send(self, req: Request, keep_text: bool = False,
             path: str = "/v1/chat/completions", body: bytes | None = None
             ) -> Request:
        body = body if body is not None else self.body(req)
        text = []
        conn = http.client.HTTPConnection(self.host, self.port,
                                          timeout=self.timeout_s)
        try:
            req.sent = self.now()
            conn.request("POST", path, body=body,
                         headers={"Content-Type": "application/json"})
            resp = conn.getresponse()
            req.status = resp.status
            if resp.status != 200:
                req.error = f"HTTP {resp.status}: " \
                            f"{resp.read(300).decode(errors='replace')}"
                return req
            saw_done = False
            for raw in resp:
                if not raw.startswith(b"data:"):
                    continue
                data = raw[5:].strip()
                if data == b"[DONE]":
                    saw_done = True
                    break
                frame = json.loads(data)
                usage = frame.get("usage")
                if usage:
                    req.prompt_tokens = usage.get("prompt_tokens")
                    req.completion_tokens = usage.get("completion_tokens")
                choices = frame.get("choices") or []
                if not choices:
                    continue
                choice = choices[0]
                piece = ((choice.get("delta") or {}).get("content")
                         or choice.get("text"))
                if piece or (choice.get("finish_reason") is not None
                             and req.first is None):
                    # a token frame; or a stream that finished with no
                    # visible text, whose finish frame is its only signal
                    now = self.now()
                    if req.first is None:
                        req.first = now
                    else:
                        req.gap_max = max(req.gap_max, now - req.last)
                    req.last = now
                    req.frame_times.append(now)
                    if keep_text and piece:
                        text.append(piece)
            if not saw_done:
                req.error = "stream ended without [DONE]"
            elif req.completion_tokens is None:
                req.error = "no usage frame"
        except (OSError, http.client.HTTPException, ValueError) as e:
            req.error = f"{type(e).__name__}: {e}"
        finally:
            req.done = self.now()
            conn.close()
            if keep_text:
                req.text = "".join(text)
        return req


# ------------------------------------------------------------ the loops --


def _sleep_until(sender: Sender, t: float) -> None:
    while True:
        left = t - sender.now()
        if left <= 0:
            return
        time.sleep(min(left, 0.5))


def run_open(sender: Sender, mix: dict, rate_rps: float, lead_in_s: float,
             seconds: float) -> list:
    """Arrivals at their due times from -lead_in_s to `seconds`, each on a
    thread of its own; then wait (bounded) for what arrived to finish."""
    plan = []
    for phase, start, dur in (("lead_in", -lead_in_s, lead_in_s),
                              ("window", 0.0, seconds)):
        times = arrival_times(mix, rate_rps, dur, phase)
        for t, (p, o) in zip(times, lengths(mix, len(times), phase)):
            plan.append(Request(len(plan), phase, start + t, p, o))
    threads = []
    for req in plan:
        _sleep_until(sender, req.due)
        th = threading.Thread(target=sender.send, args=(req,), daemon=True,
                              name=f"req-{req.idx}")
        th.start()
        threads.append(th)
    _join_bounded(sender, threads, plan, seconds + float(mix["drain_limit_s"]))
    return plan


def run_closed(sender: Sender, mix: dict, clients: int, lead_in_s: float,
               seconds: float) -> list:
    """`clients` callers, started evenly over the first half of the lead-in,
    each sending its next request when the last completes, until the window
    ends; requests in flight then are left to finish (bounded)."""
    # a cycle of length pairs, long against what a run completes
    pairs = lengths(mix, int(mix["closed_cycle"]), "closed")
    plan, lock = [], threading.Lock()

    def client(k: int) -> None:
        _sleep_until(sender, -lead_in_s + (lead_in_s / 2.0) * k / clients)
        while True:
            now = sender.now()
            if now >= seconds:
                return
            with lock:
                p, o = pairs[len(plan) % len(pairs)]
                req = Request(len(plan), "window" if now >= 0 else "lead_in",
                              now, p, o)
                plan.append(req)
            sender.send(req)

    threads = [threading.Thread(target=client, args=(k,), daemon=True,
                                name=f"client-{k}") for k in range(clients)]
    for th in threads:
        th.start()
    _join_bounded(sender, threads, plan, seconds + float(mix["drain_limit_s"]))
    return plan


def _join_bounded(sender: Sender, threads: list, plan: list,
                  until: float) -> None:
    for th in threads:
        th.join(timeout=max(0.0, until - sender.now()))
    for req in list(plan):
        if req.done is None:
            req.error = req.error or "not finished within the drain limit"


def run_mix(sender: Sender, mix: dict, load: dict, seconds: float) -> list:
    lead_in_s = float(mix["lead_in_s"])
    if mix["loop"] == "open":
        return run_open(sender, mix, float(load["rate_rps"]), lead_in_s,
                        seconds)
    if mix["loop"] == "closed":
        return run_closed(sender, mix, int(load["clients"]), lead_in_s,
                          seconds)
    raise ValueError(f"unknown loop {mix['loop']!r}")
