"""Headline benchmark: engine decode throughput in tok/s/chip.

Prints ONE JSON line: {"metric", "value", "unit", "vs_baseline", ...extras}.
Baseline: BASELINE.json north star = 2000 tok/s/chip (Llama-3-8B-class serving
on TPU v5e). Extra keys (same line, extra fields are harmless to parsers):
backend, chip, model, mfu, mbu, itl_ms, and a `secondary` dict with a
smaller-model run for cross-round comparability.

Model choice is HBM-aware: the 8B-class north-star model needs ~16 GiB of
bf16 weights, which does not fit a v5e chip (16 GiB HBM); there the 8B runs
as headline via int8 weight-only quantization (~8 GiB + KV room). Weights
are random — throughput doesn't depend on values.

The backend is initialised once, in-process
(dynamo_tpu.utils.platform.init_backend): a TPU, or the run fails. The CPU is
a rehearsal somebody asked for with BENCH_FORCE_CPU=1, and its line says so.

Env knobs: BENCH_MODEL, BENCH_BATCH, BENCH_STEPS, BENCH_PROMPT_LEN,
BENCH_MULTISTEP (fused decode steps per dispatch; 1 disables),
BENCH_GUIDED (1 = JSON-guided requests; measures grammar-mask overhead),
BENCH_QUANT (with BENCH_MODEL: none|int8|w8a8 — w8a8 is the fast
quantized mode and the v5e headline default; int8 is weight-only),
BENCH_TRACE=DIR (capture a jax.profiler/XProf trace of the timed loop),
BENCH_KV=int8 (quantized KV-cache pages; halves KV HBM),
BENCH_SPEC=ngram (n-gram speculative decoding; acceptance reported),
BENCH_PREFILL_CHUNK=N (override the engine's chunked-prefill size; 0 whole),
BENCH_REPETITIVE_PROMPTS=1 (looping prompts — the spec proposer's best case),
BENCH_FORCE_CPU, BENCH_SECONDARY=0 to skip the secondary run.
"""

from __future__ import annotations

import json
import os
import sys
import time

BASELINE_TOK_S_CHIP = 2000.0  # BASELINE.json north star


def _init_backend() -> str:
    import logging

    logging.basicConfig(level=logging.INFO, stream=sys.stderr)
    from dynamo_tpu.utils.platform import (
        enable_compile_cache, force_cpu, init_backend,
    )

    forced = bool(os.environ.get("BENCH_FORCE_CPU"))
    if forced:
        force_cpu()
    backend = init_backend()  # exits non-zero unless tpu (or cpu on purpose)
    if backend == "cpu" and not forced:
        # an inherited JAX_PLATFORMS=cpu is not a request for a CPU result
        raise SystemExit(
            "bench.py measures a TPU and JAX_PLATFORMS=cpu is set: no result. "
            "BENCH_FORCE_CPU=1 runs a CPU rehearsal (never comparable).")
    # persistent XLA compilation cache: repeat runs skip the jit compiles
    enable_compile_cache()
    return backend


def _chip_spec(device):
    """The device's row of the one chip table (profiler/systems.py) — the
    same mapping the live MFU/MBU exposition uses. MFU/MBU need datasheet
    peaks, so a TPU that is not in the table is an error, not a default."""
    from dynamo_tpu.profiler.systems import require_chip

    return require_chip(device.device_kind)


def _hbm_bytes(device) -> float | None:
    try:
        stats = device.memory_stats()
        return float(stats.get("bytes_limit") or 0) or None
    except Exception:
        return None


def _pick_models(on_tpu: bool, hbm: float | None):
    """((headline, quant), (secondary, quant)) by HBM headroom.

    The north-star model is Llama-3-8B (BASELINE.json #3). bf16 weights
    (~16.1 GiB) only fit chips with >20 GiB HBM; on a 16 GiB v5e the 8B
    STILL runs as headline via int8 weight-only quantization (~8 GiB +
    KV room) instead of silently demoting to the 1B model."""
    if os.environ.get("BENCH_MODEL"):
        headline = os.environ["BENCH_MODEL"]
        quant = os.environ.get("BENCH_QUANT", "none")
        sec = "llama-3.2-1b-instruct" if on_tpu else None
        if sec is None or sec == headline:
            return (headline, quant), None
        return (headline, quant), (sec, "none")
    if not on_tpu:
        return ("tiny-debug", "none"), None
    gib = 1024 ** 3
    if hbm is not None and hbm > 20 * gib:
        return ("meta-llama-3-8b-instruct", "none"), \
            ("llama-3.2-1b-instruct", "none")
    if hbm is not None and hbm > 12 * gib:
        # w8a8: int8 weights AND native int8 MXU matmuls — the weight-only
        # convert path is VPU-bound on v5e (~3.8x slower)
        return ("meta-llama-3-8b-instruct", "w8a8"), \
            ("llama-3.2-1b-instruct", "none")
    return ("llama-3.2-1b-instruct", "none"), None


def _effective_hbm(dev, chip) -> float | None:
    """memory_stats() when the runtime exposes it, else the catalog number
    for the identified chip (v5p etc. must still promote to the 8B model)."""
    hbm = _hbm_bytes(dev)
    if hbm is None and chip is not None:
        hbm = chip.hbm_bytes
    return hbm


def bench_model(model: str, on_tpu: bool, chip, quant: str = "none") -> dict:
    """Run steady-state decode on `model`; return metrics incl. MFU/MBU."""
    import jax

    from dynamo_tpu.engine.config import EngineConfig
    from dynamo_tpu.engine.engine import Engine
    from dynamo_tpu.engine.request import GenRequest
    from dynamo_tpu.models.config import ModelConfig
    from dynamo_tpu.profiler import roofline

    batch = int(os.environ.get("BENCH_BATCH", "64" if on_tpu else "4"))
    steps = int(os.environ.get("BENCH_STEPS", "128" if on_tpu else "32"))
    prompt_len = int(os.environ.get("BENCH_PROMPT_LEN", "128" if on_tpu else "16"))
    # multi-step decode amortises the per-dispatch host round-trip across
    # a window of fused steps
    multistep = int(os.environ.get("BENCH_MULTISTEP", "16" if on_tpu else "4"))
    max_seq = prompt_len + steps + 8

    mcfg = ModelConfig.from_model_name(
        model, dtype=None if on_tpu else "float32"
    )
    wbytes = 1 if quant in ("int8", "w8a8") else 2
    # shrink batch when weights + KV would overflow the chip
    if on_tpu and chip is not None:
        kv_seq = roofline.kv_bytes_per_token(mcfg) * max_seq
        budget = chip.hbm_bytes * 0.9 - roofline.param_count(mcfg) * wbytes
        while batch > 4 and batch * kv_seq > budget * 0.8:
            batch //= 2

    # engine-config overrides only when explicitly asked (engine defaults —
    # e.g. prefill_chunk_tokens=256 — otherwise apply unchanged)
    extra = {}
    if os.environ.get("BENCH_PREFILL_CHUNK") is not None:
        extra["prefill_chunk_tokens"] = int(os.environ["BENCH_PREFILL_CHUNK"])
    if os.environ.get("BENCH_SPEC"):
        extra["speculative_mode"] = os.environ["BENCH_SPEC"]
    # BENCH_GUIDED=1: run every request JSON-guided (response_format
    # json_object) — measures the on-device grammar-mask overhead against
    # an identical unguided run (ignore_eos keeps token counts equal)
    guided = bool(os.environ.get("BENCH_GUIDED"))
    eng = Engine(
        EngineConfig(
            model=model,
            page_size=16,
            num_pages=batch * ((max_seq + 15) // 16) + 8,
            max_num_seqs=batch,
            max_seq_len=max_seq,
            num_scheduler_steps=multistep,
            quantization=quant,
            kv_cache_dtype=os.environ.get("BENCH_KV", "auto"),
            **extra,
        ),
        model_cfg=mcfg,
    )

    if os.environ.get("BENCH_REPETITIVE_PROMPTS"):
        # short cycles: the n-gram speculative proposer's best case (and a
        # realistic stand-in for templated/structured generation). The cycle
        # LENGTH depends on the salt (8 vs 9) so timed prompts can never
        # alias warmup prompts — equal streams would need both cycles
        # constant — and the prefix cache can't absorb the timed prefills.
        def mk(i, salt):
            n = 8 + salt // 2
            base = [(i * 13 + salt * 31 + j) % 97 + 3 for j in range(n)]
            return (base * (prompt_len // n + 1))[:prompt_len]
    else:
        def mk(i, salt):
            return [(i * (7 + salt) + j * (1 + salt)) % 199 + 1
                    for j in range(prompt_len)]
    prompts = [mk(i, 0) for i in range(batch)]
    # warmup compiles prefill + BOTH decode paths (the fused multi-step window
    # needs every sequence to have >= multistep tokens of headroom, so warm
    # generations must be long enough to trigger it)
    for i, p in enumerate(prompts):
        eng.add_request(
            GenRequest(f"warm{i}", p, max_tokens=max(4, 2 * multistep),
                       temperature=0.0, ignore_eos=True,
                       guided_json=guided)
        )
    while eng.has_work:
        eng.step()
    # drop compile-time outliers from the phase histograms: the timed run's
    # TTFT/ITL percentiles must reflect steady-state serving only
    eng.reset_metrics()

    # FRESH prompts for the timed run: reusing the warmup prompts would let
    # the prefix cache absorb every prefill and report cache-hit TTFT
    timed_prompts = [mk(i, 2) for i in range(batch)]
    # independently-measured TTFT: admission -> first-token WALL clock per
    # request, sampled at the bench layer — reported alongside the engine
    # histograms so the two sources cross-check each other (a serving-
    # histogram bug can't silently skew the bench's headline percentiles)
    t_submit: dict = {}
    ttft_samples: list = []
    for i, p in enumerate(timed_prompts):
        t_submit[f"b{i}"] = time.perf_counter()
        eng.add_request(
            GenRequest(f"b{i}", p, max_tokens=steps, temperature=0.0,
                       ignore_eos=True, guided_json=guided)
        )
    # drain prefills so the timed section is pure decode steady-state
    guided_outs = {} if guided else None
    while eng.pending:
        for ev in eng.step():
            if ev.index == 0 and ev.request_id in t_submit:
                ttft_samples.append(
                    time.perf_counter() - t_submit.pop(ev.request_id))
            # pre-timed tokens still belong to the guided grammar audit
            # (a replay missing the opening tokens would start mid-JSON)
            if guided_outs is not None and ev.token_id >= 0:
                guided_outs.setdefault(ev.request_id, []).append(ev.token_id)
    jax.block_until_ready(eng.k_pages)
    # TTFT (prefill phase) was measured during the drain; re-zero only the
    # decode phases so ITL percentiles exclude the batch ramp-up steps
    eng.metrics.reset_phases("decode_window", "decode_step")

    trace_dir = os.environ.get("BENCH_TRACE")
    if trace_dir:
        # capture the steady-state decode loop for XProf (the same capture
        # /debug/trace serves in workers); parse with xprof hlo_stats
        jax.profiler.start_trace(trace_dir)
    t0 = time.perf_counter()
    tokens = 0
    itl_samples: list = []  # per-step wall time / steps advanced
    steps_before = eng.metrics.decode_steps
    while eng.has_work:
        t_step = time.perf_counter()
        step_tokens = 0
        active = max(eng.num_active, 1)
        for ev in eng.step():
            if ev.token_id >= 0:
                tokens += 1
                step_tokens += 1
                if guided_outs is not None:
                    guided_outs.setdefault(ev.request_id, []).append(
                        ev.token_id)
        if step_tokens:
            # independent per-token latency sample: this iteration's wall
            # time over the steps it advanced each sequence
            steps_adv = max(1, round(step_tokens / active))
            itl_samples.append((time.perf_counter() - t_step) / steps_adv)
    dt = time.perf_counter() - t0
    if trace_dir:
        jax.profiler.stop_trace()
    decode_steps = eng.metrics.decode_steps - steps_before

    tok_s = tokens / dt

    def _pctl(vals, q):
        if not vals:
            return 0.0
        vals = sorted(vals)
        return vals[min(len(vals) - 1, int(round(q * (len(vals) - 1))))]

    phases = eng.metrics.phases
    out = {
        "model": model,
        "tok_s_per_chip": round(tok_s, 2),  # single-chip engine
        "batch": batch,
        "itl_ms": round(1e3 * dt * batch / max(tokens, 1), 3),
        # BASELINE.json headline: tok/s/chip + p50 TTFT/ITL. TTFT ~= prefill
        # latency (admission-to-first-token); ITL from per-step timings.
        # Two sources, reported side by side (ISSUE 6 satellite): the
        # engine's serving histograms AND bench-layer wall-clock samples —
        # large disagreement flags a histogram bug or host-side stalls the
        # engine timers can't see.
        "ttft_p50_ms": phases["prefill"].quantile_ms(0.5),
        "itl_p50_ms": phases["decode_step"].quantile_ms(0.5),
        "itl_p95_ms": phases["decode_step"].quantile_ms(0.95),
        "latency_source": "engine_histogram",
        "measured": {
            "source": "bench_wall_clock",
            "ttft_p50_ms": round(1e3 * _pctl(ttft_samples, 0.5), 3),
            "ttft_p95_ms": round(1e3 * _pctl(ttft_samples, 0.95), 3),
            "itl_p50_ms": round(1e3 * _pctl(itl_samples, 0.5), 3),
            "itl_p95_ms": round(1e3 * _pctl(itl_samples, 0.95), 3),
        },
        "decode_steps_timed": decode_steps,
        # step-timeline bubble baseline: per-phase self-time shares and
        # the inter-dispatch host-gap distribution — the zero-bubble
        # work's before/after number (docs/perf.md)
        "timeline": eng.timeline.summary(),
    }
    if quant != "none":
        out["quantization"] = quant
    if guided:
        # grammar audit via the ENGINE's own vocab table (handles byte and
        # HF layouts alike): DEAD absorbs, so a stream is legal iff the
        # full replay ends anywhere but DEAD (stop ids fold as no-ops, so
        # ignore_eos's post-completion eos spam is fine)
        from dynamo_tpu.ops import json_guide as jg

        table = eng._ensure_guide_table()
        out["guided"] = True
        out["guided_legal"] = all(
            jg.replay(table, toks)[0] != jg.DEAD
            for toks in guided_outs.values())
    if eng.metrics.spec_draft_tokens:
        out["spec_drafted"] = eng.metrics.spec_draft_tokens
        out["spec_accepted"] = eng.metrics.spec_accepted_tokens
        out["spec_acceptance"] = round(
            eng.metrics.spec_accepted_tokens
            / max(eng.metrics.spec_draft_tokens, 1), 4)
    if chip is not None:
        # decode-phase utilization against datasheet peaks: MFU from the
        # roofline's active-param FLOP model, MBU from weight+KV stream bytes
        active = roofline.active_param_count(mcfg)
        avg_ctx = prompt_len + steps / 2.0
        stream = (roofline.param_count(mcfg) * wbytes
                  + batch * roofline.kv_bytes_per_token(mcfg) * avg_ctx)
        out["mfu"] = round(tok_s * 2.0 * active / chip.bf16_flops, 4)
        out["mbu"] = round((tok_s / batch) * stream / chip.hbm_bw, 4)
    return out


def bench_long_shared_prefix() -> dict:
    """KVBM scenario: two-turn shared-prefix traffic whose working set
    OVERFLOWS the device prefix cache. Turn 2 replays every conversation's
    prefix; with the host tier on, the evicted prefix pages onboard back
    from host RAM instead of re-prefilling. Runs the identical workload
    with the tier on and off and reports both turn-2 mean TTFTs plus the
    host-tier hit ratio (deterministic: temperature 0, fixed prompts).

    Env: BENCH_KVBM_CONVS (default 6), BENCH_KVBM_PREFIX_TOKENS (default
    192), BENCH_KVBM_HOST_BLOCKS (default: prefix working set)."""
    from dynamo_tpu.engine.config import EngineConfig
    from dynamo_tpu.engine.engine import Engine
    from dynamo_tpu.engine.request import GenRequest

    model = os.environ.get("BENCH_MODEL", "tiny-debug")
    convs = int(os.environ.get("BENCH_KVBM_CONVS", "6"))
    prefix_len = int(os.environ.get("BENCH_KVBM_PREFIX_TOKENS", "192"))
    page = 16
    pages_per_conv = prefix_len // page + 2
    # device pool holds ~2.5 conversations: turn 2 always misses on device
    num_pages = int(pages_per_conv * 2.5)
    host_blocks = int(os.environ.get("BENCH_KVBM_HOST_BLOCKS",
                                     str(pages_per_conv * (convs + 1))))

    def prompts(turn: int):
        out = []
        for c in range(convs):
            prefix = [(c * 13 + j * 7) % 199 + 1 for j in range(prefix_len)]
            tail = [(turn * 31 + c * 3 + j) % 199 + 1 for j in range(8)]
            out.append(prefix + tail)
        return out

    def run(host_blocks_on: int) -> dict:
        eng = Engine(EngineConfig(
            model=model, page_size=page, num_pages=num_pages,
            max_num_seqs=2, max_seq_len=prefix_len + 64,
            prefill_chunk_tokens=64, kvbm_host_blocks=host_blocks_on,
        ))
        ttfts = {1: [], 2: []}
        for turn in (1, 2):
            for i, p in enumerate(prompts(turn)):
                eng.add_request(GenRequest(f"t{turn}c{i}", p, max_tokens=2,
                                           temperature=0.0, ignore_eos=True))
                # serve one conversation at a time — the multi-turn shape
                while eng.has_work:
                    for ev in eng.step():
                        if ev.phase and ev.index == 0:
                            ttfts[turn].append(ev.phase["prefill_s"])
        out = {
            "ttft_turn1_mean_ms": round(
                1e3 * sum(ttfts[1]) / max(len(ttfts[1]), 1), 3),
            "ttft_turn2_mean_ms": round(
                1e3 * sum(ttfts[2]) / max(len(ttfts[2]), 1), 3),
        }
        if eng.kvbm is not None:
            st = eng.kvbm.stats()
            lookups = st["host_hits_total"] + st["host_misses_total"]
            out["host_hits_total"] = st["host_hits_total"]
            out["host_hit_ratio"] = round(
                st["host_hits_total"] / max(lookups, 1), 4)
            out["demoted_blocks_total"] = st["demoted_blocks_total"]
            out["onboarded_blocks_total"] = st["onboarded_blocks_total"]
        return out

    on = run(host_blocks)
    off = run(0)
    return {
        "metric": "kvbm_long_shared_prefix_ttft_turn2",
        "value": on["ttft_turn2_mean_ms"],
        "unit": "ms",
        "scenario": "long_shared_prefix",
        "model": model,
        "conversations": convs,
        "prefix_tokens": prefix_len,
        "device_pages": num_pages,
        "host_blocks": host_blocks,
        "tier_on": on,
        "tier_off": off,
        "ttft_turn2_speedup": round(
            off["ttft_turn2_mean_ms"] / max(on["ttft_turn2_mean_ms"], 1e-9),
            3),
    }


def bench_multi_tenant_skew(on_tpu: bool) -> dict:
    """Per-tenant QoS scenario: ONE aggressive tenant flooding at ~10x
    its weighted share against N well-behaved tenants on a shared engine
    (docs/robustness.md "Per-tenant QoS"). Reports per-tenant TTFT/ITL
    percentiles measured at the bench layer (wall clock per TokenEvent)
    plus the engine accountant's defer/preempt counters, A/B against the
    identical workload with QoS off. Deterministic: greedy, fixed
    prompts, single-threaded step loop.

    Env: BENCH_TENANTS (well-behaved tenant count, default 3),
    BENCH_SKEW (aggressor request multiplier, default 10),
    BENCH_QOS_TOKENS (max_tokens per request, default 32)."""
    import time as _time

    from dynamo_tpu.engine.config import EngineConfig
    from dynamo_tpu.engine.engine import Engine
    from dynamo_tpu.engine.request import GenRequest

    model = os.environ.get("BENCH_MODEL",
                           "llama-3.2-1b-instruct" if on_tpu else "tiny-debug")
    n_good = int(os.environ.get("BENCH_TENANTS", "3"))
    skew = int(os.environ.get("BENCH_SKEW", "10"))
    steps = int(os.environ.get("BENCH_QOS_TOKENS", "32"))
    tenants = [{"name": "aggressor", "weight": 1}] + [
        {"name": f"good{i}", "weight": 1} for i in range(n_good)]

    def requests():
        reqs = []
        for i in range(skew):
            reqs.append(("aggressor", f"agg{i}",
                         [(i * 13 + j * 7) % 199 + 1 for j in range(24)]))
        for i in range(n_good):
            reqs.append((f"good{i}", f"good{i}-0",
                         [(i * 31 + j * 5) % 199 + 1 for j in range(24)]))
        return reqs

    def pctl(vals, q):
        if not vals:
            return 0.0
        vals = sorted(vals)
        return vals[min(len(vals) - 1, int(round(q * (len(vals) - 1))))]

    def run(qos_on: bool, params=None):
        eng = Engine(EngineConfig(
            model=model, page_size=16, num_pages=256, max_num_seqs=4,
            max_seq_len=steps + 64, seed=11, enable_prefix_caching=False,
            tenants=json.dumps(tenants) if qos_on else "[]"), params=params)
        # warm every program the timed run can hit — the SOLO prefill
        # (QoS admissions land one by one), the batched group prefill,
        # the next bucket up (preemption continuations carry prompt +
        # output), and the decode window — so the timed section measures
        # SCHEDULING, not compiles
        eng.add_request(GenRequest(
            "warm-solo", [(j * 3) % 199 + 1 for j in range(24)],
            max_tokens=8, temperature=0.0, ignore_eos=True))
        while eng.has_work:
            eng.step()
        eng.add_request(GenRequest(
            "warm-cont", [(j * 5) % 199 + 1 for j in range(40)],
            max_tokens=8, temperature=0.0, ignore_eos=True))
        while eng.has_work:
            eng.step()
        for i in range(4):
            eng.add_request(GenRequest(
                f"warm{i}", [(i * 17 + j * 3) % 199 + 1 for j in range(24)],
                max_tokens=8, temperature=0.0, ignore_eos=True))
        while eng.has_work:
            eng.step()
        eng.reset_metrics()
        submit, first, itl, last = {}, {}, {}, {}
        for tenant, rid, prompt in requests():
            submit[rid] = (_time.perf_counter(), tenant)
            eng.add_request(GenRequest(rid, prompt, max_tokens=steps,
                                       temperature=0.0, ignore_eos=True,
                                       tenant=tenant if qos_on else None))
        while eng.has_work:
            for ev in eng.step():
                now = _time.perf_counter()
                if ev.token_id < 0:
                    continue
                t0, tenant = submit[ev.request_id]
                if ev.request_id not in first:
                    first[ev.request_id] = now - t0
                else:
                    itl.setdefault(tenant, []).append(
                        now - last[ev.request_id])
                last[ev.request_id] = now
        per_tenant = {}
        for rid, (t0, tenant) in submit.items():
            per_tenant.setdefault(tenant, {}).setdefault(
                "ttft_samples", []).append(first.get(rid, 0.0))
        out = {}
        for tenant, d in sorted(per_tenant.items()):
            samples = itl.get(tenant, [])
            out[tenant] = {
                "ttft_p50_ms": round(1e3 * pctl(d["ttft_samples"], 0.5), 3),
                "ttft_p95_ms": round(1e3 * pctl(d["ttft_samples"], 0.95), 3),
                "itl_p50_ms": round(1e3 * pctl(samples, 0.5), 3),
                "itl_p95_ms": round(1e3 * pctl(samples, 0.95), 3),
            }
        res = {"tenants": out}
        if eng.qos is not None:
            res["qos"] = eng.qos.stats()
        return res, eng.params

    qos_res, params = run(qos_on=True)
    base_res, _ = run(qos_on=False, params=params)
    good_ttft_on = [v["ttft_p95_ms"] for t, v in qos_res["tenants"].items()
                    if t != "aggressor"]
    good_ttft_off = [v["ttft_p95_ms"] for t, v in base_res["tenants"].items()
                     if t != "aggressor"]
    return {
        "metric": "multi_tenant_skew_good_ttft_p95",
        "value": max(good_ttft_on) if good_ttft_on else 0.0,
        "unit": "ms",
        "scenario": "multi_tenant_skew",
        "model": model,
        "aggressor_requests": skew,
        "well_behaved_tenants": n_good,
        "qos_on": qos_res,
        "qos_off": base_res,
        "good_ttft_p95_speedup": round(
            max(good_ttft_off) / max(max(good_ttft_on), 1e-9), 3)
        if good_ttft_off and good_ttft_on else 0.0,
        # CPU-rehearsal latency is never comparable to the TPU north star
        # (standing ROADMAP constraint)
        "comparable": bool(on_tpu),
    }


def bench_prefill_interference(on_tpu: bool) -> dict:
    """Unified-ragged-step A/B (docs/perf.md "Unified ragged step"):
    decode ITL p50/p95 for live streams while a stream of long prompts
    arrives, with the mixed step on (--mixed-batch-tokens packs each
    prefill chunk into the same program as the decode rows) vs off (the
    classic chunk/decode alternation, where every chunk is a full stall
    between decode windows). Both arms use the SAME chunk budget, so the
    A/B isolates scheduling, not chunk geometry; a first untimed pass of
    the identical traffic shape compiles every program the timed section
    hits. Reports both latency sources side by side — the engine's
    decode_step histogram (mixed steps feed it too: they ARE the ITL
    step) and bench-layer wall-clock per-step samples — plus the ragged
    composition stats. Deterministic: greedy, fixed prompts,
    single-threaded step loop.

    Env: BENCH_MIX_STREAMS (live decode streams, default 3),
    BENCH_MIX_PROMPTS (interfering long prompts, default 4),
    BENCH_MIX_PROMPT_TOKENS (default 192), BENCH_MIX_TOKENS (decode
    tokens per stream, default 48), BENCH_MIX_BUDGET (chunk/mixed token
    budget, default 64)."""
    import time as _time

    from dynamo_tpu.engine.config import EngineConfig
    from dynamo_tpu.engine.engine import Engine
    from dynamo_tpu.engine.request import GenRequest

    model = os.environ.get("BENCH_MODEL",
                           "llama-3.2-1b-instruct" if on_tpu else "tiny-debug")
    streams = int(os.environ.get("BENCH_MIX_STREAMS", "3"))
    prompts = int(os.environ.get("BENCH_MIX_PROMPTS", "4"))
    plen = int(os.environ.get("BENCH_MIX_PROMPT_TOKENS", "192"))
    steps = int(os.environ.get("BENCH_MIX_TOKENS", "48"))
    budget = int(os.environ.get("BENCH_MIX_BUDGET", "64"))

    def pctl(vals, q):
        if not vals:
            return 0.0
        vals = sorted(vals)
        return vals[min(len(vals) - 1, int(round(q * (len(vals) - 1))))]

    def run(mixed_on: bool, params=None):
        eng = Engine(EngineConfig(
            model=model, page_size=16, num_pages=512,
            max_num_seqs=streams + 1, max_seq_len=plen + steps + 96,
            seed=7, enable_prefix_caching=False,
            prefill_chunk_tokens=budget,
            mixed_batch_tokens=budget if mixed_on else 0), params=params)

        def drive(tag):
            itl = []
            for i in range(streams):
                eng.add_request(GenRequest(
                    f"{tag}-live{i}",
                    [(i * 17 + j * 3) % 199 + 1 for j in range(24)],
                    max_tokens=steps, temperature=0.0, ignore_eos=True))
            # live batch reaches steady state before interference starts
            for _ in range(streams + 2):
                eng.step()
            for i in range(prompts):
                eng.add_request(GenRequest(
                    f"{tag}-long{i}",
                    [(i * 29 + j * 7) % 199 + 1 for j in range(plen)],
                    max_tokens=1, temperature=0.0, ignore_eos=True))
            last = _time.perf_counter()
            while eng.has_work:
                evs = eng.step()
                # true ITL: time BETWEEN consecutive live-token emissions.
                # In the classic arm a chunk-only step emits no live token,
                # so its stall accrues into the next sample — that is
                # precisely the interference under test. (The engine's
                # decode_step histogram cannot see it: chunks are a
                # separate phase there.)
                if any(e.request_id.startswith(f"{tag}-live")
                       and e.token_id >= 0 for e in evs):
                    now = _time.perf_counter()
                    itl.append(now - last)
                    last = now
            return itl

        drive("warm")  # compile everything the timed shape hits
        eng.reset_metrics()
        itl = drive("timed")
        ph = eng.metrics.phases["decode_step"]
        snap = eng.metrics.snapshot()
        res = {
            "engine": {
                "source": "engine_histogram",
                "itl_p50_ms": ph.quantile_ms(0.5),
                "itl_p95_ms": ph.quantile_ms(0.95),
            },
            "measured": {
                "source": "bench_wall_clock",
                "itl_p50_ms": round(1e3 * pctl(itl, 0.5), 3),
                "itl_p95_ms": round(1e3 * pctl(itl, 0.95), 3),
            },
            "mixed_steps": eng.metrics.mixed_count,
            "mixed_frac_mean": snap["mixed_frac_mean"],
            "chunk_steps": eng.metrics.phases["prefill_chunk"].count,
            # recorded zero-bubble baseline for this arm: host-gap
            # distribution + per-phase shares (step timeline)
            "timeline": eng.timeline.summary(),
        }
        for d in (res["engine"], res["measured"]):
            d["itl_p95_p50_ratio"] = round(
                d["itl_p95_ms"] / max(d["itl_p50_ms"], 1e-9), 3)
        return res, eng.params

    on_res, params = run(True)
    off_res, _ = run(False, params=params)
    return {
        "metric": "prefill_interference_itl_p95",
        # headline uses the wall-clock source: only it sees the classic
        # arm's chunk stalls between decode steps (engine histogram books
        # those under prefill_chunk, not decode_step)
        "value": on_res["measured"]["itl_p95_ms"],
        "unit": "ms",
        "scenario": "prefill_interference",
        "model": model,
        "live_streams": streams,
        "long_prompts": prompts,
        "prompt_tokens": plen,
        "mixed_budget_tokens": budget,
        "mixed_on": on_res,
        "mixed_off": off_res,
        "itl_p95_speedup": round(
            off_res["measured"]["itl_p95_ms"]
            / max(on_res["measured"]["itl_p95_ms"], 1e-9), 3),
        # CPU-rehearsal latency is never comparable to the TPU north star
        # (standing ROADMAP constraint)
        "comparable": bool(on_tpu),
    }


def bench_speculative_agentic(on_tpu: bool) -> dict:
    """Speculation three-arm A/B (docs/perf.md "Speculation v3"): per-token
    ITL for agentic/tool-loop streams with speculation OFF vs the N-GRAM
    drafter vs the MODEL drafter, all at the SAME mixed-batch budget, so
    the arms isolate the proposer, not scheduling. Prompts are a repeated
    tool-call template — the history self-similarity n-gram drafting feeds
    on — so the model arm's edge shows up where prompt-lookup misses
    (window boundaries, prompt-to-output transitions, non-repeating
    spans). Long prompts arrive mid-run in every arm: with spec on, the
    speculating slots ride the unified ragged mixed step as K+1-wide rows
    next to the prefill chunks (the composition this scenario exists to
    exercise). A first untimed pass of the identical traffic shape
    compiles every program the timed section hits.

    The model arm defaults to SELF-drafting (the draft model is the
    target model sharing the target's weights): on the CPU gate that is
    the only same-tokenizer pair available, and it measures the plumbing
    cost at the acceptance CEILING a perfectly-matched draft model would
    reach. Set BENCH_SPEC_DRAFT_MODEL to a real smaller same-tokenizer
    model on TPU to measure a production pair.

    Reports both latency sources side by side — the engine's decode_step
    histogram (per STEP: a verify step that lands n tokens still books one
    step) and bench-layer wall-clock per-TOKEN ITL (step gap divided by
    live tokens emitted, the number a client actually sees) — plus each
    arm's acceptance-length histogram (the `drafter`-labeled
    dynamo_engine_spec_accept_length series) and the ngram->model mean
    shift the drafter comparison reads. Deterministic: greedy, fixed
    prompts, single-threaded step loop.

    Env: BENCH_SPEC_STREAMS (live decode streams, default 3),
    BENCH_SPEC_TOKENS (decode tokens per stream, default 64),
    BENCH_SPEC_K (draft tokens per window, default 4), BENCH_SPEC_BUDGET
    (mixed/chunk token budget, default 64), BENCH_SPEC_PROMPTS
    (interfering long prompts, default 2), BENCH_SPEC_PROMPT_TOKENS
    (default 128), BENCH_SPEC_DRAFT_MODEL (model arm's draft model,
    default = the target model, self-drafting)."""
    import time as _time

    from dynamo_tpu.engine.config import EngineConfig
    from dynamo_tpu.engine.engine import Engine
    from dynamo_tpu.engine.request import GenRequest

    model = os.environ.get("BENCH_MODEL",
                           "llama-3.2-1b-instruct" if on_tpu else "tiny-debug")
    draft_model = os.environ.get("BENCH_SPEC_DRAFT_MODEL", model)
    streams = int(os.environ.get("BENCH_SPEC_STREAMS", "3"))
    steps = int(os.environ.get("BENCH_SPEC_TOKENS", "64"))
    k = int(os.environ.get("BENCH_SPEC_K", "4"))
    budget = int(os.environ.get("BENCH_SPEC_BUDGET", "64"))
    prompts = int(os.environ.get("BENCH_SPEC_PROMPTS", "2"))
    plen = int(os.environ.get("BENCH_SPEC_PROMPT_TOKENS", "128"))

    def pctl(vals, q):
        if not vals:
            return 0.0
        vals = sorted(vals)
        return vals[min(len(vals) - 1, int(round(q * (len(vals) - 1))))]

    def agentic_prompt(i):
        # tool-loop shape: one short call/result template repeated — the
        # history self-similarity prompt-lookup drafting feeds on
        block = [(i * 13 + t) % 97 + 1 for t in range(8)]
        return block * 6

    def run(arm: str, params=None):
        eng = Engine(EngineConfig(
            model=model, page_size=16, num_pages=512,
            max_num_seqs=streams + 1, max_seq_len=plen + steps + 96,
            seed=7, enable_prefix_caching=False,
            prefill_chunk_tokens=budget, mixed_batch_tokens=budget,
            speculative_mode="off" if arm == "off" else arm,
            draft_model=draft_model if arm == "model" else None,
            num_speculative_tokens=k), params=params)
        if arm == "model" and draft_model == model:
            # self-drafting: share the target's weights so the draft
            # chain IS the target chain (the acceptance ceiling); the
            # separately-initialized draft params are dropped
            eng.draft.params = eng.params

        def drive(tag):
            itl = []
            for i in range(streams):
                eng.add_request(GenRequest(
                    f"{tag}-live{i}", agentic_prompt(i), max_tokens=steps,
                    temperature=0.0, ignore_eos=True))
            for _ in range(streams + 2):
                eng.step()
            for i in range(prompts):
                eng.add_request(GenRequest(
                    f"{tag}-long{i}",
                    [(i * 29 + j * 7) % 199 + 1 for j in range(plen)],
                    max_tokens=1, temperature=0.0, ignore_eos=True))
            last = _time.perf_counter()
            while eng.has_work:
                evs = eng.step()
                # per-TOKEN ITL: a verify step that lands n accepted
                # tokens at once is n tokens of progress for one step's
                # wall time — exactly the speedup speculation buys
                n = sum(1 for e in evs
                        if e.request_id.startswith(f"{tag}-live")
                        and e.token_id >= 0)
                if n:
                    now = _time.perf_counter()
                    itl.extend([(now - last) / n] * n)
                    last = now
            return itl

        drive("warm")  # compile everything the timed shape hits
        eng.reset_metrics()
        itl = drive("timed")
        ph = eng.metrics.phases["decode_step"]
        m = eng.metrics
        snap = m.snapshot()
        res = {
            "engine": {
                "source": "engine_histogram",
                "step_p50_ms": ph.quantile_ms(0.5),
                "step_p95_ms": ph.quantile_ms(0.95),
            },
            "measured": {
                "source": "bench_wall_clock",
                "itl_p50_ms": round(1e3 * pctl(itl, 0.5), 3),
                "itl_p95_ms": round(1e3 * pctl(itl, 0.95), 3),
                "itl_mean_ms": round(
                    1e3 * sum(itl) / max(len(itl), 1), 3),
                # unrounded mean for the speedup ratios (the rounded
                # display value can hit 0.000 on sub-us CPU steps)
                "_itl_mean_raw": 1e3 * sum(itl) / max(len(itl), 1),
            },
            "decode_steps": eng.metrics.decode_steps,
            "output_tokens": eng.metrics.output_tokens,
        }
        if arm != "off":
            # the drafter-labeled acceptance-length histogram, verbatim
            # from the series dynamo_engine_spec_accept_length{drafter}
            # exposes — the right-shift between the ngram and model arms
            # is the drafter comparison's acceptance evidence
            buckets = m.spec_hist_by.get(arm, [])
            res["spec"] = {
                "drafter": arm,
                "draft_tokens": snap["spec_draft_tokens"],
                "accepted_tokens": snap["spec_accepted_tokens"],
                "acceptance_rate": (
                    round(snap["spec_accepted_tokens"]
                          / snap["spec_draft_tokens"], 4)
                    if snap["spec_draft_tokens"] else 0.0),
                "accept_len_mean": snap["spec_accept_mean"],
                "accept_len_hist": {
                    "edges": list(m._SPEC_EDGES),
                    "counts": list(buckets),
                },
            }
            if eng.draft is not None:
                ds = eng.draft.stats()
                res["spec"]["draft_engine"] = {
                    key: ds[key] for key in
                    ("num_pages", "draft_steps", "catchup_tokens",
                     "rollbacks", "evictions")}
        return res, eng.params

    ngram_res, params = run("ngram")
    model_res, _ = run("model", params=params)
    off_res, _ = run("off", params=params)
    shift = round(model_res["spec"]["accept_len_mean"]
                  - ngram_res["spec"]["accept_len_mean"], 4)
    speedup_ngram = round(
        off_res["measured"]["_itl_mean_raw"]
        / max(ngram_res["measured"]["_itl_mean_raw"], 1e-9), 3)
    speedup_model = round(
        off_res["measured"]["_itl_mean_raw"]
        / max(model_res["measured"]["_itl_mean_raw"], 1e-9), 3)
    for r in (off_res, ngram_res, model_res):
        del r["measured"]["_itl_mean_raw"]
    return {
        "metric": "speculative_agentic_itl_mean",
        # headline uses the wall-clock per-token source of the MODEL arm:
        # the engine histogram books one entry per STEP and so cannot see
        # the multi-token windows the speedup comes from
        "value": model_res["measured"]["itl_mean_ms"],
        "unit": "ms",
        "scenario": "speculative_agentic",
        "model": model,
        "draft_model": draft_model,
        "live_streams": streams,
        "decode_tokens": steps,
        "num_speculative_tokens": k,
        "mixed_budget_tokens": budget,
        "spec_off": off_res,
        "spec_ngram": ngram_res,
        "spec_model": model_res,
        # ngram -> model right-shift of the acceptance-length histogram
        # mean (positive = the draft model lands longer windows than
        # prompt-lookup on the same traffic at the same budget)
        "accept_len_shift": shift,
        "itl_speedup_ngram": speedup_ngram,
        "itl_speedup_model": speedup_model,
        # CPU-rehearsal latency is never comparable to the TPU north star
        # (standing ROADMAP constraint); on CPU the model arm's
        # draft-forward cost also runs on the wrong silicon
        "comparable": bool(on_tpu),
    }


def bench_batch_soak(on_tpu: bool) -> dict:
    """Preemptible-batch-tier A/B (docs/robustness.md "Preemptible batch
    tier"): a diurnal-shaped interactive load — bursts separated by
    troughs — with the batch lane ON (a standing offline backlog soaks
    the trough chips, QoS-evicted the step interactive returns) vs OFF
    (the troughs idle). Reports chip-seconds utilization over the run's
    wall clock from the engine cost ledger, the per-TIER cost-ledger
    rows (the chargeback evidence that batch work priced as batch), and
    interactive ITL p95 both arms — the tier's contract is that the
    utilization gain costs the interactive tail nothing.

    Env: BENCH_SOAK_CYCLES (bursts, default 3), BENCH_SOAK_BURST
    (interactive requests per burst, default 3), BENCH_SOAK_TROUGH_S
    (trough wall seconds, default 0.4), BENCH_SOAK_TOKENS (interactive
    max_tokens, default 24), BENCH_SOAK_BACKLOG (standing batch
    requests, default 8)."""
    import time as _time

    from dynamo_tpu.engine.config import EngineConfig
    from dynamo_tpu.engine.engine import Engine
    from dynamo_tpu.engine.request import GenRequest

    model = os.environ.get("BENCH_MODEL",
                           "llama-3.2-1b-instruct" if on_tpu else "tiny-debug")
    cycles = int(os.environ.get("BENCH_SOAK_CYCLES", "3"))
    burst = int(os.environ.get("BENCH_SOAK_BURST", "3"))
    trough_s = float(os.environ.get("BENCH_SOAK_TROUGH_S", "0.4"))
    steps = int(os.environ.get("BENCH_SOAK_TOKENS", "24"))
    backlog = int(os.environ.get("BENCH_SOAK_BACKLOG", "8"))
    tenants = [{"name": "batch", "weight": 1, "batch": True},
               {"name": "live", "weight": 3}]

    def pctl(vals, q):
        if not vals:
            return 0.0
        vals = sorted(vals)
        return vals[min(len(vals) - 1, int(round(q * (len(vals) - 1))))]

    def run(batch_on: bool, params=None):
        eng = Engine(EngineConfig(
            model=model, page_size=16, num_pages=256, max_num_seqs=4,
            max_seq_len=4 * steps + 96, seed=11,
            enable_prefix_caching=False,
            tenants=json.dumps(tenants)), params=params)
        # warm the programs the timed section hits: solo prefill, batched
        # group prefill, the continuation bucket (eviction recompute
        # carries prompt + output), and the decode window
        eng.add_request(GenRequest(
            "warm-solo", [(j * 3) % 199 + 1 for j in range(24)],
            max_tokens=8, temperature=0.0, ignore_eos=True))
        while eng.has_work:
            eng.step()
        eng.add_request(GenRequest(
            "warm-cont", [(j * 5) % 199 + 1 for j in range(40)],
            max_tokens=8, temperature=0.0, ignore_eos=True))
        while eng.has_work:
            eng.step()
        for i in range(4):
            eng.add_request(GenRequest(
                f"warm{i}", [(i * 17 + j * 3) % 199 + 1 for j in range(24)],
                max_tokens=8, temperature=0.0, ignore_eos=True))
        while eng.has_work:
            eng.step()
        eng.reset_metrics()
        # the cost ledger is monotonic: measure the timed section by delta
        roll0 = eng.cost.rollup()
        tiers0 = roll0.get("tiers", {})
        chip0 = roll0["totals"]["chip_seconds"]
        itl, last = [], {}
        batch_tokens = [0]
        t0 = _time.perf_counter()
        if batch_on:
            for i in range(backlog):
                eng.add_request(GenRequest(
                    f"batch{i}", [(i * 29 + j * 11) % 199 + 1
                                  for j in range(24)],
                    max_tokens=3 * steps, temperature=0.0, ignore_eos=True,
                    tenant="batch"))

        def pump(live_left):
            for ev in eng.step():
                now = _time.perf_counter()
                if ev.token_id < 0:
                    continue
                if ev.request_id.startswith("live"):
                    if ev.request_id in last:
                        itl.append(now - last[ev.request_id])
                    last[ev.request_id] = now
                elif ev.request_id.startswith("batch"):
                    batch_tokens[0] += 1
                if ev.finished:
                    live_left.discard(ev.request_id)

        for c in range(cycles):
            live_left = set()
            for b in range(burst):
                rid = f"live{c}-{b}"
                live_left.add(rid)
                eng.add_request(GenRequest(
                    rid, [(c * 31 + b * 7 + j * 5) % 199 + 1
                          for j in range(24)],
                    max_tokens=steps, temperature=0.0, ignore_eos=True,
                    tenant="live"))
            while live_left:
                pump(live_left)
            # the trough: the batch lane soaks the idle chips, the
            # no-batch arm idles for the same wall window
            t_end = _time.perf_counter() + trough_s
            while _time.perf_counter() < t_end:
                if eng.has_work:
                    pump(set())
                else:
                    _time.sleep(0.005)
        wall = _time.perf_counter() - t0
        roll = eng.cost.rollup()
        tier_rows = {}
        for tier, row in roll.get("tiers", {}).items():
            base = tiers0.get(tier, {})
            tier_rows[tier] = {
                k: round(v - base.get(k, 0.0), 6) for k, v in row.items()}
        chip_s = roll["totals"]["chip_seconds"] - chip0
        return {
            "wall_s": round(wall, 3),
            "chip_seconds": round(chip_s, 6),
            "chip_utilization": round(chip_s / max(wall, 1e-9), 4),
            "batch_tokens": batch_tokens[0],
            "interactive_itl_p50_ms": round(1e3 * pctl(itl, 0.5), 3),
            "interactive_itl_p95_ms": round(1e3 * pctl(itl, 0.95), 3),
            "cost_tiers": tier_rows,
        }, eng.params

    on_res, params = run(batch_on=True)
    off_res, _ = run(batch_on=False, params=params)
    return {
        "metric": "batch_soak_chip_utilization",
        "value": on_res["chip_utilization"],
        "unit": "chip_s_per_wall_s",
        "scenario": "batch_soak",
        "model": model,
        "cycles": cycles,
        "burst": burst,
        "trough_s": trough_s,
        "batch_backlog": backlog,
        "batch_on": on_res,
        "batch_off": off_res,
        "utilization_gain": round(
            on_res["chip_utilization"]
            / max(off_res["chip_utilization"], 1e-9), 3),
        "interactive_itl_p95_ratio": round(
            on_res["interactive_itl_p95_ms"]
            / max(off_res["interactive_itl_p95_ms"], 1e-9), 3),
        # CPU-rehearsal latency is never comparable to the TPU north star
        # (standing ROADMAP constraint)
        "comparable": bool(on_tpu),
    }


def bench_rolling_update(on_tpu: bool) -> dict:
    """Live-elasticity A/B (docs/robustness.md "Hitless weight
    rollout"): the same stream load served twice — the ROLLOUT arm
    stages v2 into the double buffer and arms a finish-mode flip halfway
    through the run while decode continues, the STEADY arm never touches
    the weights. Reports completed/dropped streams both arms (the
    acceptance is dropped == 0 across the flip), ITL p50/p95, the
    worst single inter-token gap (the flip-stall ceiling: staging is
    section-by-section host→HBM copy OFF the decode path, so the gap
    must look like the steady arm's), host-side stage seconds, and the
    staged-buffer high-water bytes (the double-buffer HBM cost).

    Env: BENCH_ROLL_STREAMS (total streams, default 10000 on TPU / 12 on
    CPU), BENCH_ROLL_TOKENS (max_tokens per stream, default 24)."""
    import time as _time

    from dynamo_tpu.engine.config import EngineConfig
    from dynamo_tpu.engine.engine import Engine
    from dynamo_tpu.engine.request import GenRequest

    model = os.environ.get("BENCH_MODEL",
                           "llama-3.2-1b-instruct" if on_tpu else "tiny-debug")
    streams = int(os.environ.get("BENCH_ROLL_STREAMS",
                                 "10000" if on_tpu else "12"))
    steps = int(os.environ.get("BENCH_ROLL_TOKENS", "24"))

    def pctl(vals, q):
        if not vals:
            return 0.0
        vals = sorted(vals)
        return vals[min(len(vals) - 1, int(round(q * (len(vals) - 1))))]

    def run(rollout: bool, params=None):
        eng = Engine(EngineConfig(
            model=model, page_size=16, num_pages=256, max_num_seqs=4,
            max_seq_len=steps + 96, seed=11,
            enable_prefix_caching=False), params=params)
        wm = eng.weights
        # warm solo + batched prefill and the decode window so the timed
        # section never eats a compile (the flip itself recompiles
        # NOTHING: same tree structure, new leaf values)
        for i in range(4):
            eng.add_request(GenRequest(
                f"warm{i}", [(i * 17 + j * 3) % 199 + 1 for j in range(24)],
                max_tokens=8, temperature=0.0, ignore_eos=True))
        while eng.has_work:
            eng.step()
        itl, last = [], {}
        done = [0]
        flip_at = streams // 2
        admitted = [0]
        staged_bytes = 0
        stage_s = 0.0
        t0 = _time.perf_counter()

        def admit_next():
            i = admitted[0]
            if i >= streams:
                return False
            eng.add_request(GenRequest(
                f"s{i}", [(i * 31 + j * 5) % 199 + 1 for j in range(24)],
                max_tokens=steps, temperature=0.0, ignore_eos=True))
            admitted[0] += 1
            return True

        for _ in range(min(4, streams)):
            admit_next()
        flipped = False
        while eng.has_work or admitted[0] < streams:
            if rollout and not flipped and done[0] >= flip_at:
                # mid-run: stage v2 while v1 keeps decoding, then arm a
                # finish-mode flip — in-flight streams complete on v1,
                # later admissions land on v2
                wm.stage("v2", seed=123)
                staged_bytes = wm.staged_nbytes
                stage_s = wm.stats()["last_stage_s"]
                wm.flip(mode="finish")
                flipped = True
            for ev in eng.step():
                now = _time.perf_counter()
                if ev.token_id >= 0:
                    if ev.request_id in last:
                        itl.append(now - last[ev.request_id])
                    last[ev.request_id] = now
                if ev.finished and ev.request_id.startswith("s"):
                    done[0] += 1
                    admit_next()
            if not eng.has_work and admitted[0] < streams:
                admit_next()
        wall = _time.perf_counter() - t0
        if rollout:
            wm.commit()
        return {
            "wall_s": round(wall, 3),
            "streams": streams,
            "completed": done[0],
            "dropped": streams - done[0],
            "itl_p50_ms": round(1e3 * pctl(itl, 0.5), 3),
            "itl_p95_ms": round(1e3 * pctl(itl, 0.95), 3),
            "itl_max_ms": round(1e3 * max(itl, default=0.0), 3),
            "final_version": wm.version,
            "stage_s": round(stage_s, 3),
            "staged_bytes_high_water": staged_bytes,
        }, eng.params

    roll_res, params = run(rollout=True)
    steady_res, _ = run(rollout=False, params=params)
    return {
        "metric": "rolling_update_dropped_streams",
        "value": roll_res["dropped"],
        "unit": "streams",
        "scenario": "rolling_update",
        "model": model,
        "streams": streams,
        "rollout": roll_res,
        "steady": steady_res,
        "itl_p95_ratio": round(
            roll_res["itl_p95_ms"]
            / max(steady_res["itl_p95_ms"], 1e-9), 3),
        "flip_stall_ratio": round(
            roll_res["itl_max_ms"]
            / max(steady_res["itl_max_ms"], 1e-9), 3),
        # CPU-rehearsal latency is never comparable to the TPU north star
        # (standing ROADMAP constraint)
        "comparable": bool(on_tpu),
    }


def bench_engine_chaos(on_tpu: bool) -> dict:
    """Engine watchdog A/B (docs/robustness.md "Engine watchdog &
    quarantine"): the same interactive stream load served twice — the
    CHAOS arm takes sub-deadline device slowness (engine.device_slow,
    must NOT trip the watchdog) plus one NaN-poisoned canary stream
    mid-run (the integrity sentinel must abort exactly the canary), the
    STEADY arm runs fault-free. Headline: interactive streams dropped
    across the chaos (the acceptance is 0 — sentinels abort poisoned
    streams, never co-tenants) with the ITL p95 ratio as the
    degraded-silicon latency guard. The chaos arm also times one
    in-place engine resurrection after the run drains (the
    pod-replacement-avoided number).

    Env: BENCH_CHAOS_STREAMS (total interactive streams, default 2000 on
    TPU / 12 on CPU), BENCH_CHAOS_TOKENS (max_tokens, default 24)."""
    import time as _time

    from dynamo_tpu.engine.config import EngineConfig
    from dynamo_tpu.engine.engine import Engine
    from dynamo_tpu.engine.request import GenRequest
    from dynamo_tpu.robustness import faults

    model = os.environ.get("BENCH_MODEL",
                           "llama-3.2-1b-instruct" if on_tpu else "tiny-debug")
    streams = int(os.environ.get("BENCH_CHAOS_STREAMS",
                                 "2000" if on_tpu else "12"))
    steps = int(os.environ.get("BENCH_CHAOS_TOKENS", "24"))

    def pctl(vals, q):
        if not vals:
            return 0.0
        vals = sorted(vals)
        return vals[min(len(vals) - 1, int(round(q * (len(vals) - 1))))]

    def run(chaos: bool, params=None):
        plane = faults.reset_plane()
        eng = Engine(EngineConfig(
            model=model, page_size=16, num_pages=256, max_num_seqs=4,
            max_seq_len=steps + 96, seed=11,
            enable_prefix_caching=False), params=params)
        for i in range(4):
            eng.add_request(GenRequest(
                f"warm{i}", [(i * 17 + j * 3) % 199 + 1 for j in range(24)],
                max_tokens=8, temperature=0.0, ignore_eos=True))
        while eng.has_work:
            eng.step()
        itl, last = [], {}
        done, bad = [0], [0]
        admitted = [0]
        canary = {"hold": False, "sent": False, "pending": False,
                  "reason": None}
        slow_at, nan_at = streams // 3, streams // 2
        t0 = _time.perf_counter()

        def admit_next():
            if canary["hold"] and not canary["sent"] or canary["pending"]:
                return False  # the poisoned prefill must ride alone
            i = admitted[0]
            if i >= streams:
                return False
            eng.add_request(GenRequest(
                f"s{i}", [(i * 31 + j * 5) % 199 + 1 for j in range(24)],
                max_tokens=steps, temperature=0.0, ignore_eos=True))
            admitted[0] += 1
            return True

        for _ in range(min(4, streams)):
            admit_next()
        while eng.has_work or admitted[0] < streams:
            if chaos and done[0] >= slow_at and not plane.snapshot()[
                    "fired_total"].get("engine.device_slow"):
                # degraded silicon: slow-but-alive readbacks, well under
                # the deadline — the watchdog must NOT trip
                plane.configure({"engine.device_slow":
                                 {"times": 3, "delay_s": 0.004}})
            if chaos and done[0] >= nan_at and not canary["sent"]:
                # one corrupted forward, aimed at a canary admission:
                # interactive admissions hold until every earlier prefill
                # is installed, so the NaN can only hit the canary
                canary["hold"] = True
                if not eng.pending and eng._inflight is None:
                    plane.configure({"engine.device_nan": {"times": 1}})
                    eng.add_request(GenRequest(
                        "canary", [(j * 7) % 199 + 1 for j in range(24)],
                        max_tokens=steps, temperature=0.0,
                        ignore_eos=True))
                    canary["sent"] = canary["pending"] = True
            for ev in eng.step():
                now = _time.perf_counter()
                if ev.request_id == "canary":
                    if ev.finished:
                        canary["pending"] = False
                        canary["reason"] = ev.finish_reason
                    continue
                if ev.token_id >= 0:
                    if ev.request_id in last:
                        itl.append(now - last[ev.request_id])
                    last[ev.request_id] = now
                if ev.finished and ev.request_id.startswith("s"):
                    done[0] += 1
                    if ev.finish_reason not in ("length", "stop"):
                        bad[0] += 1  # a co-tenant was harmed: a drop
                    admit_next()
            if not eng.has_work and admitted[0] < streams:
                admit_next()
        wall = _time.perf_counter() - t0
        wd = eng.watchdog.summary()
        resurrect_s = None
        if chaos:
            # the run is drained: time one in-place resurrection (what a
            # suspect engine pays instead of a pod replacement)
            t1 = _time.perf_counter()
            eng.watchdog.on_fatal_step(RuntimeError("bench-injected"))
            resurrect_s = _time.perf_counter() - t1
        plane.clear()
        return {
            "wall_s": round(wall, 3),
            "streams": streams,
            "completed": done[0] - bad[0],
            "dropped": streams - done[0] + bad[0],
            "itl_p50_ms": round(1e3 * pctl(itl, 0.5), 3),
            "itl_p95_ms": round(1e3 * pctl(itl, 0.95), 3),
            "itl_max_ms": round(1e3 * max(itl, default=0.0), 3),
            "trips_total": wd["trips_total"],
            "integrity_faults_total": wd["integrity_faults_total"],
            "canary_finish_reason": canary["reason"],
            "health_after": eng.watchdog.health,
            "resurrect_s": (round(resurrect_s, 3)
                            if resurrect_s is not None else None),
        }, eng.params

    chaos_res, params = run(chaos=True)
    steady_res, _ = run(chaos=False, params=params)
    return {
        "metric": "engine_chaos_dropped_streams",
        "value": chaos_res["dropped"],
        "unit": "streams",
        "scenario": "engine_chaos",
        "model": model,
        "streams": streams,
        "chaos": chaos_res,
        "steady": steady_res,
        "itl_p95_ratio": round(
            chaos_res["itl_p95_ms"]
            / max(steady_res["itl_p95_ms"], 1e-9), 3),
        # the contract, machine-checkable: sub-deadline slowness tripped
        # nothing, the sentinel caught exactly the canary, and the
        # post-run resurrection came back healthy
        "false_positive_trips": sum(
            chaos_res["trips_total"].get(k, 0)
            for k in ("hung_dispatch",)),
        "canary_aborted": chaos_res["canary_finish_reason"]
        == "integrity_fault",
        "resurrected_healthy": chaos_res["health_after"] == "healthy",
        # CPU-rehearsal latency is never comparable to the TPU north star
        # (standing ROADMAP constraint)
        "comparable": bool(on_tpu),
    }


def main() -> None:
    backend = _init_backend()
    import jax

    on_tpu = backend not in ("cpu",)
    if os.environ.get("BENCH_SCENARIO") == "long_shared_prefix":
        # KVBM tier A/B: one JSON line, same contract as the headline
        print(json.dumps(bench_long_shared_prefix()))
        return
    if os.environ.get("BENCH_SCENARIO") == "multi_tenant_skew":
        # per-tenant QoS isolation A/B: one JSON line, same contract
        print(json.dumps(bench_multi_tenant_skew(on_tpu)))
        return
    if os.environ.get("BENCH_SCENARIO") == "prefill_interference":
        # unified ragged step A/B: one JSON line, same contract
        print(json.dumps(bench_prefill_interference(on_tpu)))
        return
    if os.environ.get("BENCH_SCENARIO") == "speculative_agentic":
        # speculative decoding v2 A/B: one JSON line, same contract
        print(json.dumps(bench_speculative_agentic(on_tpu)))
        return
    if os.environ.get("BENCH_SCENARIO") == "batch_soak":
        # preemptible batch tier A/B: one JSON line, same contract
        print(json.dumps(bench_batch_soak(on_tpu)))
        return
    if os.environ.get("BENCH_SCENARIO") == "rolling_update":
        # hitless weight rollout A/B: one JSON line, same contract
        print(json.dumps(bench_rolling_update(on_tpu)))
        return
    if os.environ.get("BENCH_SCENARIO") == "engine_chaos":
        # engine watchdog A/B: one JSON line, same contract
        print(json.dumps(bench_engine_chaos(on_tpu)))
        return
    dev = jax.devices()[0]
    chip = _chip_spec(dev) if on_tpu else None
    hbm = _effective_hbm(dev, chip) if on_tpu else None

    headline, secondary = _pick_models(on_tpu, hbm)
    res = bench_model(headline[0], on_tpu, chip, quant=headline[1])
    sec = None
    if secondary and os.environ.get("BENCH_SECONDARY", "1") != "0":
        sec = bench_model(secondary[0], on_tpu, chip, quant=secondary[1])

    line = {
        "metric": f"decode_throughput_{res['model']}_{backend}",
        "value": res["tok_s_per_chip"],
        "unit": "tok/s/chip",
        # the north star is a TPU target; a CPU rehearsal must not claim
        # a ratio against it
        "vs_baseline": round(res["tok_s_per_chip"] / BASELINE_TOK_S_CHIP, 4)
        if on_tpu else 0.0,
        "backend": backend,
        "chip": getattr(dev, "device_kind", str(dev)),
        "model": res["model"],
        "batch": res["batch"],
        "itl_ms": res["itl_ms"],
        # the non-comparability flag lives HERE, next to both latency
        # sources: CPU-rehearsal percentiles must never be compared to the
        # TPU north star (standing ROADMAP constraint)
        "comparable": bool(on_tpu),
    }
    for k in ("mfu", "mbu", "quantization", "ttft_p50_ms", "itl_p50_ms",
              "itl_p95_ms", "measured", "timeline", "spec_drafted",
              "spec_accepted", "spec_acceptance", "guided", "guided_legal"):
        if k in res:
            line[k] = res[k]
    if not on_tpu:
        line["note"] = ("cpu rehearsal forced via BENCH_FORCE_CPU — value "
                        "not comparable to the TPU north star")
    if sec is not None:
        line["secondary"] = sec
    print(json.dumps(line))


if __name__ == "__main__":
    main()
