"""Starts and stops the system under test: one `python -m
dynamo_tpu.frontend` and one worker as child processes of a parent that never
imports JAX (a process that has touched JAX holds the chip, and the worker
needs it). The pattern is chip_smoke.py's, proven on the chip at PR 21.
"""

from __future__ import annotations

import json
import os
import signal
import socket
import subprocess
import sys
import time
import urllib.request

from .spec import REPO

READY_TIMEOUT_S = 1100.0     # a cold /ready took 606-644 s (PERF.md, PR 21)
LOG_DIR = os.path.join(REPO, "chiprun_out", "bench", "logs")


class ServerFailure(Exception):
    pass


def log(msg: str) -> None:
    print(f"[bench {time.strftime('%H:%M:%S')}] {msg}", file=sys.stderr,
          flush=True)


def free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def get(url: str, timeout: float = 10.0) -> bytes:
    with urllib.request.urlopen(url, timeout=timeout) as r:
        return r.read()


def get_json(url: str, timeout: float = 10.0) -> dict:
    return json.loads(get(url, timeout))


class Child:
    """One child process in its own session, logging to a file."""

    def __init__(self, name: str, argv: list, env: dict):
        os.makedirs(LOG_DIR, exist_ok=True)
        self.name = name
        self.log_path = os.path.join(LOG_DIR, f"{name}.log")
        self._log = open(self.log_path, "wb")
        self.proc = subprocess.Popen(
            argv, env=env, cwd=REPO, stdout=self._log,
            stderr=subprocess.STDOUT, start_new_session=True)

    def tail(self, n: int = 30) -> str:
        self._log.flush()
        try:
            with open(self.log_path, "rb") as f:
                return "\n".join(
                    f.read().decode(errors="replace").splitlines()[-n:])
        except OSError:
            return ""

    def alive(self) -> bool:
        return self.proc.poll() is None

    def terminate(self, timeout_s: float) -> int:
        """SIGTERM, then wait for the graceful exit; its exit code."""
        self.proc.send_signal(signal.SIGTERM)
        try:
            return self.proc.wait(timeout=timeout_s)
        except subprocess.TimeoutExpired:
            raise ServerFailure(
                f"{self.name} did not exit within {timeout_s:.0f}s of "
                f"SIGTERM:\n{self.tail()}")

    def kill(self) -> None:
        """Last resort: the whole session dies, and is waited for."""
        if self.proc.poll() is None:
            try:
                os.killpg(self.proc.pid, signal.SIGKILL)
            except ProcessLookupError:
                pass
            self.proc.wait(timeout=30)
        self._log.close()


def metric_labels(text: str, name: str) -> dict:
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


def device_of(stats: dict) -> dict:
    """The result line's `device`, as JAX reports it in the worker; the
    memory peak is that of the fullest chip."""
    return {"platform": stats["platform"], "kind": stats["device_kind"],
            "count": stats["device_count"],
            "memory_peak_bytes": max(
                int(d["peak_bytes_in_use"])
                for d in stats["memory"]["devices"])}


class Server:
    """The frontend and the worker of one configuration. `with Server(...)`
    stops both whatever happens."""

    def __init__(self, config: dict, model_dir: str, seed: int,
                 variant: str | None = None):
        self.config = config
        self.variant = (config["variants"][variant] if variant else {})
        self.model_name = config["name"]
        self.model = self.variant.get("model") or model_dir
        self.seed = seed
        self.children: list = []
        self.fport = self.wport = None
        self.worker = self.frontend = None

    @property
    def frontend_url(self) -> str:
        return f"http://127.0.0.1:{self.fport}"

    @property
    def worker_url(self) -> str:
        return f"http://127.0.0.1:{self.wport}"

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        for c in self.children:
            c.kill()

    def start(self) -> None:
        env = dict(os.environ)
        env["PYTHONUNBUFFERED"] = "1"
        env["DRAIN_TIMEOUT_S"] = "10"
        # The children are TOLD their platform: the TPU first, so that JAX
        # fails at start-up without one and an inherited JAX_PLATFORMS=cpu
        # cannot turn a measurement into a CPU run.
        env["JAX_PLATFORMS"] = self.variant.get("jax_platforms", "tpu,cpu")
        env.pop("XLA_FLAGS", None)
        self.fport, self.wport = free_port(), free_port()
        self.frontend = Child("frontend", [
            sys.executable, "-m", "dynamo_tpu.frontend",
            "--host", "127.0.0.1", "--port", str(self.fport)], env)
        self.children.append(self.frontend)
        flags = self.variant.get("worker_flags", self.config["worker_flags"])
        self.worker = Child("worker", [
            sys.executable, "-m", self.config["worker_module"],
            "--model", self.model, "--served-model-name", self.model_name,
            "--host", "127.0.0.1", "--port", str(self.wport),
            "--frontend-url", self.frontend_url,
            "--heartbeat-interval", "1",
            # weights from the seed; the engine keeps it in 31 bits
            "--seed", str(self.seed % 2147483647),
            *flags], env)
        self.children.append(self.worker)
        log(f"started frontend :{self.fport} and worker :{self.wport} "
            f"({self.model}, JAX_PLATFORMS={env['JAX_PLATFORMS']}); "
            f"logs in {LOG_DIR}")

    def wait_ready(self) -> None:
        """Until /ready answers and the frontend lists the model. A child
        that dies (no accelerator, no package beside us) ends the run."""
        t_end = time.monotonic() + READY_TIMEOUT_S
        ready = False
        while time.monotonic() < t_end:
            for c in self.children:
                if not c.alive():
                    raise ServerFailure(
                        f"{c.name} exited with code {c.proc.returncode} "
                        f"before the server was ready:\n{c.tail()}")
            try:
                if not ready:
                    get(self.worker_url + "/ready", 5.0)
                    ready = True
                ids = [m["id"] for m in
                       get_json(self.frontend_url + "/v1/models")["data"]]
                if self.model_name in ids:
                    return
            except (OSError, ValueError, KeyError):
                pass
            time.sleep(0.5)
        raise ServerFailure(
            f"timed out after {READY_TIMEOUT_S:.0f}s waiting for the server "
            f"(worker ready: {ready}):\n{self.worker.tail()}")

    def stats(self) -> dict:
        return get_json(self.worker_url + "/worker/stats", 20.0)

    def fallbacks(self) -> dict:
        """{(op, reason): count} of dynamo_pallas_fallback_total."""
        text = get(self.worker_url + "/metrics", 20.0).decode()
        return {(dict(k)["op"], dict(k)["reason"]): int(v)
                for k, v in metric_labels(
                    text, "dynamo_pallas_fallback_total").items()}

    def stop(self) -> list:
        """SIGTERM both; the problems found, if any."""
        problems = []
        for child, limit in ((self.worker, 60.0), (self.frontend, 30.0)):
            rc = child.terminate(limit)
            if rc != 0:
                problems.append(f"{child.name} exited {rc} after SIGTERM:\n"
                                f"{child.tail(15)}")
        return problems
