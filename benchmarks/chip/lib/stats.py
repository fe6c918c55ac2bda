"""Arithmetic from per-request records to numbers. Copies of the sound parts
of benchmarks/utils/benchmark.py (`_pctl`), kept here so that no later PR
can change the yardstick."""

from __future__ import annotations

import math
import statistics


def percentile(values, q: float) -> float:
    """Nearest-rank percentile on the sorted sample (q in 0..100). Raises on
    an empty sample: a metric with nothing under it is a fault, not a 0."""
    if not values:
        raise ValueError("percentile of an empty sample")
    ordered = sorted(values)
    idx = int(round(q / 100.0 * (len(ordered) - 1)))
    return ordered[min(len(ordered) - 1, max(0, idx))]


def quartile_spread(values) -> float:
    """(Q3 - Q1) / median, with Python's statistics.quantiles(n=4): the
    spread the builder's contract sets bounds from."""
    q1, _, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / statistics.median(values)


class Request:
    """What the client's clock saw of one request. Times are seconds on
    time.monotonic(), relative to the start of the measured window."""

    __slots__ = ("idx", "phase", "due", "sent", "first", "last", "done",
                 "frame_times", "gap_max", "want_prompt",
                 "want_out", "prompt_tokens", "completion_tokens", "status",
                 "error", "text")

    def __init__(self, idx: int, phase: str, due: float, want_prompt: int,
                 want_out: int):
        self.idx = idx
        self.phase = phase            # "lead_in" | "window"
        self.due = due
        self.sent = self.first = self.last = self.done = None
        self.frame_times = []     # one per token frame that arrived
        self.gap_max = 0.0
        self.want_prompt = want_prompt
        self.want_out = want_out
        self.prompt_tokens = self.completion_tokens = None
        self.status = 0
        self.error = ""
        self.text = None              # kept for the probe only

    @property
    def ok(self) -> bool:
        return self.status == 200 and not self.error

    @property
    def exact(self) -> bool:
        """Carries exactly the tokens asked for, in and out."""
        return (self.completion_tokens == self.want_out
                and self.prompt_tokens == self.want_prompt)

    def ttft_s(self, fail_s: float) -> float:
        """First streamed token minus the time the request was DUE. A
        failed request misses every limit: it reads as `fail_s`."""
        if not self.ok or self.first is None:
            return fail_s
        return self.first - self.due

    def tpot_s(self, fail_s: float):
        """(last token - first token) / (tokens - 1): the request's own
        mean token time. None for a one-token answer."""
        if not self.ok or self.first is None:
            return fail_s
        if (self.completion_tokens or 0) < 2:
            return None
        return (self.last - self.first) / (self.completion_tokens - 1)


def end_to_end(requests, window_s: float, setup_s: float,
               fail_s: float) -> dict:
    """Every end-to-end metric this benchmark knows, from the requests that
    were due in the window (tails) and the tokens streamed in it (rate). The
    caller prints the ones the cell owes."""
    win = [r for r in requests if r.phase == "window"]
    out = {"setup_s": setup_s}
    if win:
        ttft = [r.ttft_s(fail_s) * 1e3 for r in win]
        tpot = [t * 1e3 for t in (r.tpot_s(fail_s) for r in win)
                if t is not None]
        out["ttft_p50_ms"] = percentile(ttft, 50)
        out["ttft_p95_ms"] = percentile(ttft, 95)
        if tpot:
            out["tpot_p95_ms"] = percentile(tpot, 95)
        # all the token gaps of the window's requests over all their time;
        # a failed request is one gap of `fail_s`
        spans = [(r.last - r.first, r.completion_tokens - 1)
                 if r.ok and r.first is not None else (fail_s, 1)
                 for r in win]
        gaps = sum(n for _, n in spans)
        if gaps:
            out["tpot_mean_ms"] = 1e3 * sum(t for t, _ in spans) / gaps
    # every token that reached a client inside the window, whichever request
    # it belongs to (one frame a token: the mixes make every token visible):
    # all the work and all the time of the window. Counting only requests
    # that COMPLETED in it would quantise the rate by whole requests.
    tokens = sum(1 for r in requests if r.ok for t in r.frame_times
                 if 0.0 <= t <= window_s)
    if tokens:
        out["out_tokens_per_s"] = tokens / window_s
    return out


def lateness(requests) -> dict:
    """How late the generator ran (sent - due), so that a starved generator
    is not read as a fast server."""
    late = [(r.sent - r.due) * 1e3 for r in requests
            if r.phase == "window" and r.sent is not None]
    if not late:
        return {}
    return {"n": len(late), "p50_ms": percentile(late, 50),
            "p95_ms": percentile(late, 95), "max_ms": max(late)}


def finite(x) -> bool:
    return isinstance(x, (int, float)) and not isinstance(x, bool) \
        and math.isfinite(x)
