#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that the system still starts on the chip.

Drives the main path once, the way a user would: one `python -m
dynamo_tpu.frontend` process (no JAX), one `python -m dynamo_tpu.vllm_tpu`
worker process that owns the chip(s), registered with the frontend by
heartbeat; Qwen2.5-7B at its published widths, w8a8, random weights from a
seed, byte tokenizer; warmup ON. Then a little traffic through the frontend
that reaches every program family (full and chunked prefill, 1-step and
fused decode windows, the mixed prefill+decode step, prefix-cache suffix
prefill, guided-JSON windows, logprobs twins), and a graceful drain.

    python chip_smoke.py              # one chip (what the driver runs)
    python chip_smoke.py --chips 4    # one worker, --tp 4, same traffic
    python chip_smoke.py --rehearse-cpu   # tiny-debug on the CPU: debugs
                                          # this script, proves nothing

This parent process never imports jax: a process that has touched JAX holds
the chip, and the worker needs it. The children are told JAX_PLATFORMS=
tpu,cpu explicitly, so an inherited JAX_PLATFORMS=cpu cannot turn the smoke
into a CPU run; with no TPU the worker exits at backend init and so does
this script, non-zero, with the worker's reason.

It passes (exit 0, last stdout line `{"ok": true, "device": {...}}`) only
if every request came back 200 with the token count asked for, the worker
reports platform "tpu" and a device kind found in the one chip table
(dynamo_tpu/profiler/systems.py), the watchdog is healthy with no trips and
no integrity faults and its hang deadline armed once warmup completed, no
program was compiled after warmup, the only Pallas->XLA demotions are the
ones listed in EXPECTED_FALLBACKS (none), decode / prefill / chunk / ragged
attention were traced as `pallas`, and both children exit 0 after SIGTERM.
The line before last is the full summary (set-up reported apart from
serving; no throughput figure is derived: this is a smoke, not a benchmark).
"""

from __future__ import annotations

import argparse
import concurrent.futures
import json
import math
import os
import signal
import socket
import subprocess
import sys
import time
import urllib.error
import urllib.request

REPO = os.path.dirname(os.path.abspath(__file__))

MODEL = "qwen2.5-7b-instruct"
REHEARSAL_MODEL = "tiny-debug"

# 1.5 kB prompts + their outputs need > 1024 positions; 2048 keeps the
# prefill bucket count at 8 (16..2048) so a cold warmup — every bucket,
# chunk width, window, mixed and guided twin — fits the smoke's time limit.
MAX_SEQ_LEN = 2048
# 32k tokens of bf16 KV (~1.9 GB at 28 layers x 4 KV heads x 128): room for
# 8 concurrent 1.5 kB prompts with no preemption beside ~7.6 GB of weights
NUM_PAGES = 2048
MAX_NUM_SEQS = 8

# Pallas->XLA demotions the smoke expects, by (op, reason). Anything else in
# dynamo_pallas_fallback_total fails the run.
EXPECTED_FALLBACKS: set = set()
# the rehearsal forces interpret-mode kernels onto tiny-debug, whose fused
# KV*D lane span (2 x 32) is below the 128-lane DMA tile: decode, chunk and
# ragged attention demote at the lane gate there (and only there)
REHEARSAL_FALLBACKS = EXPECTED_FALLBACKS | {
    ("decode", "lane_gate"), ("chunk attention", "lane_gate"),
    ("ragged attention", "lane_gate"),
}

# a cold /ready (46 programs at 7B widths, empty compile cache) took
# 606-617 s on one chip and 676 s on four (PERF.md)
READY_TIMEOUT_S = 960.0
REQUEST_TIMEOUT_S = 180.0
DRAIN_TIMEOUT_S = 60.0


class SmokeFailure(Exception):
    pass


T0 = time.monotonic()


def log(msg: str) -> None:
    print(f"[chip_smoke +{time.monotonic() - T0:6.1f}s] {msg}",
          file=sys.stderr, flush=True)


# ------------------------------------------------------------ processes --


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


class Child:
    """One child process in its own session, logging to a file."""

    def __init__(self, name: str, argv: list, env: dict, log_dir: str):
        self.name = name
        self.log_path = os.path.join(log_dir, f"{name}.log")
        self._log = open(self.log_path, "wb")
        self.proc = subprocess.Popen(
            argv, env=env, cwd=REPO, stdout=self._log,
            stderr=subprocess.STDOUT, start_new_session=True)

    def tail(self, n: int = 40) -> str:
        self._log.flush()
        try:
            with open(self.log_path, "rb") as f:
                lines = f.read().decode(errors="replace").splitlines()
        except OSError:
            return ""
        return "\n".join(lines[-n:])

    def alive(self) -> bool:
        return self.proc.poll() is None

    def terminate(self, timeout_s: float) -> int:
        """SIGTERM and wait for the graceful exit; returns the exit code."""
        self.proc.send_signal(signal.SIGTERM)
        try:
            return self.proc.wait(timeout=timeout_s)
        except subprocess.TimeoutExpired:
            raise SmokeFailure(
                f"{self.name} did not exit within {timeout_s:.0f}s of "
                f"SIGTERM:\n{self.tail()}")

    def kill(self) -> None:
        """Last resort, for the finally block: the whole session dies."""
        if self.proc.poll() is None:
            try:
                os.killpg(self.proc.pid, signal.SIGKILL)
            except ProcessLookupError:
                pass
            self.proc.wait(timeout=30)
        self._log.close()


# ----------------------------------------------------------------- http --


def _get(url: str, timeout: float = 10.0) -> bytes:
    with urllib.request.urlopen(url, timeout=timeout) as r:
        return r.read()


def _get_json(url: str, timeout: float = 10.0) -> dict:
    return json.loads(_get(url, timeout))


def _json_request(url: str, payload: dict) -> urllib.request.Request:
    return urllib.request.Request(
        url, data=json.dumps(payload).encode(),
        headers={"Content-Type": "application/json"})


def _post(url: str, payload: dict):
    """(status, parsed json or raw text)."""
    try:
        with urllib.request.urlopen(_json_request(url, payload),
                                    timeout=REQUEST_TIMEOUT_S) as r:
            return r.status, json.loads(r.read())
    except urllib.error.HTTPError as e:
        return e.code, e.read().decode(errors="replace")[:500]


def _post_sse(url: str, payload: dict):
    """(status, [decoded data frames], saw [DONE])."""
    frames, done = [], False
    try:
        with urllib.request.urlopen(_json_request(url, payload),
                                    timeout=REQUEST_TIMEOUT_S) as r:
            for raw in r:
                line = raw.decode(errors="replace").strip()
                if not line.startswith("data:"):
                    continue
                data = line[5:].strip()
                if data == "[DONE]":
                    done = True
                    break
                frames.append(json.loads(data))
            return r.status, frames, done
    except urllib.error.HTTPError as e:
        return e.code, [e.read().decode(errors="replace")[:500]], False


def _wait_for(what: str, probe, timeout_s: float, children) -> float:
    """Poll `probe()` until it returns truthy; fail fast if a child died."""
    t0 = time.monotonic()
    last_err = None
    while time.monotonic() - t0 < timeout_s:
        for c in children:
            if not c.alive():
                raise SmokeFailure(
                    f"{c.name} exited with code {c.proc.returncode} while "
                    f"waiting for {what}:\n{c.tail()}")
        try:
            if probe():
                return time.monotonic() - t0
        except (OSError, ValueError) as e:  # not up yet / partial body
            last_err = e
        time.sleep(1.0)
    raise SmokeFailure(f"timed out after {timeout_s:.0f}s waiting for "
                       f"{what} (last error: {last_err})")


def _metric_labels(text: str, name: str) -> dict:
    """{labels-tuple: value} for one Prometheus series family."""
    out = {}
    for line in text.splitlines():
        if not line.startswith(name) or line.startswith("#"):
            continue
        head, _, value = line.rpartition(" ")
        labels = ()
        if "{" in head:
            inner = head[head.index("{") + 1:head.rindex("}")]
            labels = tuple(sorted(
                (kv.split("=", 1)[0], kv.split("=", 1)[1].strip('"'))
                for kv in inner.split(",") if "=" in kv))
        elif head != name:
            continue
        out[labels] = float(value)
    return out


# -------------------------------------------------------------- traffic --


def _filler(tag: str, n_bytes: int) -> str:
    """Deterministic ASCII of exactly n_bytes, unique from its first bytes
    (so no two prompts share a KV page unless the smoke means them to)."""
    words = ("tensor", "page", "window", "chunk", "decode", "prefill",
             "router", "mesh", "cache", "token", "batch", "shard")
    out = [f"[{tag}]"]
    i = sum(map(ord, tag))
    while sum(len(w) + 1 for w in out) < n_bytes:
        out.append(words[i % len(words)])
        i = i * 7 + 3
    return " ".join(out)[:n_bytes].ljust(n_bytes, ".")


class Traffic:
    def __init__(self, frontend_url: str, model: str):
        self.url = frontend_url
        self.model = model
        self.sent = self.ok = self.tokens_out = 0
        self.failures: list = []

    def _chat(self, content: str, max_tokens: int, **extra) -> dict:
        return {"model": self.model,
                "messages": [{"role": "user", "content": content}],
                "max_tokens": max_tokens, "temperature": 0.0,
                "ignore_eos": True, **extra}

    def _record(self, name: str, status: int, body, want_tokens: int,
                exact: bool = True) -> dict:
        """Count one request; returns the body when it was right."""
        self.sent += 1
        if status != 200:
            self.failures.append(f"{name}: HTTP {status}: {body}")
            return {}
        got = (body.get("usage") or {}).get("completion_tokens")
        right = (got == want_tokens if exact
                 else got is not None and 1 <= got <= want_tokens)
        if not right:
            self.failures.append(
                f"{name}: completion_tokens {got}, asked for "
                f"{'' if exact else '<= '}{want_tokens}")
            return {}
        self.ok += 1
        self.tokens_out += got
        return body

    def short_chat(self) -> None:
        body = self._chat("Say hello.", 8)
        status, out = _post(self.url + "/v1/chat/completions", body)
        out = self._record("short_chat", status, out, 8)
        if out and out["choices"][0]["message"]["content"] is None:
            self.failures.append("short_chat: no message content")

    def long_stream(self) -> None:
        """>= 1,000 prompt bytes (>= 4 prefill chunks of 256), 64 tokens
        out, streamed with usage."""
        body = self._chat(_filler("stream", 1100), 64, stream=True,
                          stream_options={"include_usage": True})
        status, frames, done = _post_sse(
            self.url + "/v1/chat/completions", body)
        usage = next((f["usage"] for f in reversed(frames)
                      if isinstance(f, dict) and f.get("usage")), None)
        out = self._record("long_stream", status, {"usage": usage} if
                           status == 200 else frames, 64)
        if out:
            chunks = [f for f in frames if f.get("choices")]
            if not done or len(chunks) < 2:
                self.failures.append(
                    f"long_stream: {len(chunks)} content frames, "
                    f"[DONE] seen: {done}")

    def concurrent_mix(self) -> None:
        """8 at once, prompts 16 B .. 1.5 kB, mixed output lengths:
        continuous batching, the mixed prefill+decode step (long prompts
        stream in while short ones decode) and fused windows all run."""
        shapes = [(16, 24), (48, 40), (120, 16), (260, 48),
                  (400, 32), (700, 20), (1000, 28), (1500, 36)]

        def one(i_shape):
            i, (n_bytes, n_out) = i_shape
            return i, n_out, _post(
                self.url + "/v1/chat/completions",
                self._chat(_filler(f"mix{i}", n_bytes), n_out))

        with concurrent.futures.ThreadPoolExecutor(len(shapes)) as pool:
            for i, n_out, (status, out) in pool.map(one, enumerate(shapes)):
                self._record(f"concurrent_mix[{i}]", status, out, n_out)

    def repeated_prompt(self, stats) -> None:
        """The same 600-byte prompt twice: the second pass must be served
        from the prefix cache (`prefix_cache` block of /worker/stats)."""
        body = self._chat(_filler("repeat", 600), 4)
        status, out = _post(self.url + "/v1/chat/completions", body)
        self._record("repeated_prompt[0]", status, out, 4)
        before = stats()["prefix_cache"]
        status, out = _post(self.url + "/v1/chat/completions", body)
        self._record("repeated_prompt[1]", status, out, 4)
        after = stats()["prefix_cache"]
        if not (after["hits"] > before["hits"]
                and after["cached_tokens_served"]
                > before["cached_tokens_served"]):
            self.failures.append(
                f"repeated_prompt: no prefix-cache hit on the second pass "
                f"(before {before}, after {after})")

    def guided_json(self) -> None:
        """response_format json_object, twice. (1) With '{' and '}' biased
        up, greedy decoding must give exactly the dict the grammar allows:
        the mask forces '{' first ('}' is illegal there, whatever its
        bias), then '}' closes, then ONLY end-of-sequence is legal — three
        grammar states checked, independent of the random weights. (2)
        Free-running at temperature 1.5 for 48 tokens: exercises the fused
        guided window; the text must open an object, and parse if it
        closed."""
        rf = {"response_format": {"type": "json_object"}}
        body = self._chat("Return JSON.", 16, ignore_eos=False,
                          logit_bias={str(ord("{")): 100, str(ord("}")): 100},
                          **rf)
        status, out = _post(self.url + "/v1/chat/completions", body)
        out = self._record("guided_json[closed]", status, out, 16,
                           exact=False)
        if out:
            text = out["choices"][0]["message"]["content"]
            try:
                ok = isinstance(json.loads(text), dict)
            except (TypeError, ValueError):
                ok = False
            if not ok or out["choices"][0]["finish_reason"] != "stop":
                self.failures.append(
                    f"guided_json[closed]: text {text!r} "
                    f"(finish_reason "
                    f"{out['choices'][0]['finish_reason']!r}) is not a "
                    f"finished JSON object")
        body = self._chat("Return JSON.", 48, ignore_eos=False,
                          temperature=1.5, top_p=1.0, seed=4, **rf)
        status, out = _post(self.url + "/v1/chat/completions", body)
        out = self._record("guided_json[free]", status, out, 48, exact=False)
        if out:
            text = out["choices"][0]["message"]["content"] or ""
            closed = out["choices"][0]["finish_reason"] == "stop"
            try:
                ok = (isinstance(json.loads(text), dict) if closed
                      else text.lstrip().startswith("{"))
            except ValueError:
                ok = False
            if not ok:
                self.failures.append(
                    f"guided_json[free]: text {text[:80]!r} breaks the "
                    f"JSON grammar (closed: {closed})")

    def with_logprobs(self) -> None:
        body = self._chat("Count to three.", 8, logprobs=True,
                          top_logprobs=3)
        status, out = _post(self.url + "/v1/chat/completions", body)
        out = self._record("with_logprobs", status, out, 8)
        if out:
            entries = (out["choices"][0].get("logprobs") or {}).get(
                "content") or []
            good = len(entries) == 8 and all(
                isinstance(e.get("logprob"), (int, float))
                and math.isfinite(e["logprob"]) and e["logprob"] <= 0.0
                and len(e.get("top_logprobs") or []) == 3 for e in entries)
            if not good:
                self.failures.append(
                    f"with_logprobs: expected 8 finite entries with 3 "
                    f"alternatives each, got {entries[:2]}...")


# ----------------------------------------------------------------- main --


def _cache_dir() -> str:
    """Where the children's compile cache lives — the same resolution as
    dynamo_tpu.utils.platform.enable_compile_cache, without importing jax."""
    from dynamo_tpu.utils.platform import COMPILE_CACHE_ENV, build_home

    return os.environ.get(COMPILE_CACHE_ENV) or os.path.join(
        build_home(), "jax-comp-cache")


def _cache_entries(path: str) -> int:
    try:
        return sum(1 for n in os.listdir(path) if not n.endswith("-atime"))
    except OSError:
        return 0


def run(args) -> dict:
    try:
        from dynamo_tpu.profiler.systems import chip_for_device_kind
    except ImportError as e:
        raise SmokeFailure(
            f"chip_smoke.py drives the dynamo_tpu package beside it and "
            f"cannot find it ({e}); run it from a checkout") from e

    rehearsal = args.rehearse_cpu
    model = REHEARSAL_MODEL if rehearsal else MODEL
    log_dir = os.path.join(REPO, "chiprun_out", "chip_smoke")
    os.makedirs(log_dir, exist_ok=True)
    cache_dir = _cache_dir()
    cache_before = _cache_entries(cache_dir)

    env = dict(os.environ)
    env["PYTHONUNBUFFERED"] = "1"
    env["DRAIN_TIMEOUT_S"] = "30"
    # The children are TOLD which platform to use. Default: the TPU, with
    # the CPU backend beside it for host staging — JAX fails at start-up
    # if the first platform listed cannot initialise, so an inherited
    # JAX_PLATFORMS=cpu (this repo's sandboxes export it) can never turn
    # the smoke into a CPU run. Rehearsal: the CPU, by name.
    env["JAX_PLATFORMS"] = "cpu" if rehearsal else "tpu,cpu"
    env.pop("XLA_FLAGS", None)

    fport, wport = _free_port(), _free_port()
    furl, wurl = f"http://127.0.0.1:{fport}", f"http://127.0.0.1:{wport}"
    worker_argv = [
        sys.executable, "-m", "dynamo_tpu.vllm_tpu",
        "--model", model, "--host", "127.0.0.1", "--port", str(wport),
        "--frontend-url", furl, "--heartbeat-interval", "1",
        "--max-seq-len", str(MAX_SEQ_LEN),
        "--num-pages", str(NUM_PAGES), "--max-num-seqs", str(MAX_NUM_SEQS),
        "--tp", str(args.chips),
        # beyond the vllm_tpu profile, whose defaults leave two program
        # families unbuilt: the fused lax.scan window and the unified
        # mixed prefill+decode step
        "--num-scheduler-steps", "16", "--mixed-batch-tokens", "256",
    ]
    if rehearsal:
        worker_argv += ["--attention-backend", "pallas_interpret"]
    else:
        worker_argv += ["--quantization", "w8a8"]

    children: list = []
    summary: dict = {}
    try:
        frontend = Child("frontend", [
            sys.executable, "-m", "dynamo_tpu.frontend",
            "--host", "127.0.0.1", "--port", str(fport)], env, log_dir)
        children.append(frontend)
        worker = Child("worker", worker_argv, env, log_dir)
        children.append(worker)
        log(f"started frontend :{fport} and worker :{wport} "
            f"({model}, tp={args.chips}, JAX_PLATFORMS="
            f"{env['JAX_PLATFORMS']}); logs in {log_dir}")

        # urlopen raises (and _wait_for retries) until /ready answers 200
        ready_s = _wait_for("the worker's /ready",
                            lambda: _get(wurl + "/ready", 5.0) is not None,
                            READY_TIMEOUT_S, children)
        log(f"worker ready after {ready_s:.0f}s")

        def registered():
            ids = [m["id"] for m in _get_json(furl + "/v1/models")["data"]]
            return model in ids

        _wait_for(f"{model} in the frontend's /v1/models", registered, 30.0,
                  children)

        def stats() -> dict:
            return _get_json(wurl + "/worker/stats")

        warm = stats()
        platform = warm["platform"]
        want_platform = "cpu" if rehearsal else "tpu"
        if platform != want_platform:
            raise SmokeFailure(
                f"worker reports platform {platform!r}, wanted "
                f"{want_platform!r}")
        log(f"worker on {warm['device_count']} x {warm['device_kind']}; "
            f"warmup {warm['warmup']}")

        traffic = Traffic(furl, model)
        t_serve = time.monotonic()
        for phase in (traffic.short_chat, traffic.long_stream,
                      traffic.concurrent_mix,
                      lambda: traffic.repeated_prompt(stats),
                      traffic.guided_json, traffic.with_logprobs):
            phase()
            if not worker.alive():
                raise SmokeFailure(
                    f"worker died during traffic:\n{worker.tail()}")
        serving_s = time.monotonic() - t_serve
        log(f"traffic done: {traffic.ok}/{traffic.sent} ok in "
            f"{serving_s:.1f}s")

        after = stats()
        metrics_text = _get(wurl + "/metrics").decode()
        fallbacks = {
            (dict(k)["op"], dict(k)["reason"]): int(v)
            for k, v in _metric_labels(
                metrics_text, "dynamo_pallas_fallback_total").items()}
        jit_programs = _metric_labels(
            metrics_text, "dynamo_engine_jit_programs").get(())

        failures = list(traffic.failures)
        health = after["health"]
        if (health["state"] != "healthy" or health["trips_total"]
                or health["integrity_faults_total"]):
            failures.append(f"watchdog not clean: {health}")
        if not rehearsal and health["ewma_s"] is None:
            # seams feed the EWMA only while the derived hang deadline is
            # armed, which a completed warmup() on an accelerator does
            failures.append(f"hang deadline not armed after warmup: {health}")
        if not rehearsal and chip_for_device_kind(
                after["device_kind"]) is None:
            failures.append(
                f"device kind {after['device_kind']!r} is not in the chip "
                f"table (dynamo_tpu/profiler/systems.py)")
        if after["device_count"] < args.chips:
            failures.append(
                f"{after['device_count']} device(s) for --chips {args.chips}")
        programs_warm = warm["compiled_programs"]
        programs_after = after["compiled_programs"]
        if programs_after != programs_warm or (
                jit_programs is not None
                and int(jit_programs) != programs_warm):
            failures.append(
                f"compiled after warmup: {programs_warm} programs at "
                f"/ready, {programs_after} after traffic "
                f"(/metrics says {jit_programs})")
        allowed = REHEARSAL_FALLBACKS if rehearsal else EXPECTED_FALLBACKS
        unexpected = sorted(set(fallbacks) - allowed)
        if unexpected:
            failures.append(f"unexpected Pallas->XLA demotions: {unexpected} "
                            f"(all: {fallbacks})")
        traced = after["attention"]["traced"]
        if not rehearsal:
            for op in ("decode", "prefill", "chunk attention",
                       "ragged attention"):
                if set(traced.get(op, {})) != {"pallas"}:
                    failures.append(
                        f"{op} attention traced as {traced.get(op)}, "
                        f"wanted pallas only")
        devices = after["memory"]["devices"]
        in_use = [d["bytes_in_use"] for d in devices]
        if args.chips > 1 and not rehearsal:
            # weights + KV split over the model axis: every chip holds about
            # 1/chips of the total. A chip near zero holds no shard; a chip
            # far above the mean holds replicated weights or an unsharded
            # upload (by default those land whole on device 0).
            mean = sum(in_use) / len(in_use)
            for d in devices:
                if not 0.75 * mean <= d["bytes_in_use"] <= 1.25 * mean:
                    failures.append(
                        f"uneven placement: {d['device']} holds "
                        f"{d['bytes_in_use']} bytes, mean {mean:.0f} "
                        f"(all: {in_use})")

        rc = worker.terminate(DRAIN_TIMEOUT_S)
        if rc != 0:
            failures.append(f"worker exited {rc} after SIGTERM:\n"
                            f"{worker.tail(15)}")
        rc = frontend.terminate(30.0)
        if rc != 0:
            failures.append(f"frontend exited {rc} after SIGTERM:\n"
                            f"{frontend.tail(15)}")

        summary = {
            "platform": platform,
            "device_kind": after["device_kind"],
            "device_count": after["device_count"],
            "chips_used": args.chips,
            "versions": after["versions"],
            "model": model,
            "quantization": after["config"]["quantization"],
            "max_seq_len": MAX_SEQ_LEN,
            "setup": {
                "ready_wall_s": round(ready_s, 1),
                "warmup_s": (warm["warmup"] or {}).get("seconds"),
                "programs": programs_warm,
            },
            "serving": {
                "requests_sent": traffic.sent,
                "requests_ok": traffic.ok,
                "requests_failed": traffic.sent - traffic.ok,
                "tokens_out": traffic.tokens_out,
                "wall_s": round(serving_s, 1),
                "programs_after": programs_after,
            },
            "attention": {"traced": traced, "fallbacks": [
                {"op": op, "reason": reason, "count": n}
                for (op, reason), n in sorted(fallbacks.items())]},
            "health": {k: health[k] for k in (
                "state", "trips_total", "integrity_faults_total",
                "ewma_s", "deadline_s")},
            "prefix_cache": after.get("prefix_cache"),
            "peak_hbm_bytes": max(
                (d["peak_bytes_in_use"] for d in devices), default=0),
            "bytes_in_use_per_device": in_use,
            "compile_cache": {
                "dir": cache_dir,
                "env_placed": bool(os.environ.get(
                    "JAX_COMPILATION_CACHE_DIR")),
                "entries_before": cache_before,
                "entries_after": _cache_entries(cache_dir)},
        }
        if failures:
            print(json.dumps({"summary": summary}), flush=True)
            raise SmokeFailure("\n".join(failures))
        return summary
    finally:
        for c in children:
            c.kill()


def main(argv=None) -> int:
    p = argparse.ArgumentParser(
        prog="chip_smoke.py", description=__doc__.split("\n\n")[0])
    p.add_argument("--chips", type=int, default=1, choices=(1, 4),
                   help="chips the one worker drives (--tp); default 1")
    p.add_argument("--rehearse-cpu", action="store_true",
                   help="debug THIS SCRIPT on the CPU with tiny-debug and "
                        "interpret-mode kernels; never prints the pass line")
    args = p.parse_args(argv)
    try:
        summary = run(args)
    except SmokeFailure as e:
        print(f"chip_smoke: FAILED\n{e}", flush=True)
        return 1
    finally:
        assert "jax" not in sys.modules, \
            "chip_smoke's parent imported jax; it must leave the chip alone"
    print(json.dumps({"summary": summary}), flush=True)
    device = {"platform": summary["platform"],
              "kind": summary["device_kind"],
              "count": summary["device_count"]}
    if args.rehearse_cpu:
        # a rehearsal proves the script, not the system: no "ok" key
        print(json.dumps({"rehearsal": "passed", "device": device}),
              flush=True)
    else:
        print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
