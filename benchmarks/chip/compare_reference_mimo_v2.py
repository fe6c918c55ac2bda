#!/usr/bin/env python3
"""MiMo-V2.5's cut on the chip against its float32 reference, at the cell's own
sizes: what the engine's own programs give, logit for logit, inside the
sliding window and past several turns of a sliding layer's ring.

    python benchmarks/chip/compare_reference_mimo_v2.py --seed <n> [--variant cpu]

Two child processes, one after the other (a chip belongs to one process):

1. `engine`: the cell's configuration through `dynamo_tpu.engine.Engine` with
   the worker's flags (w8a8, 64 slots, 24,576 pages, 256-token mixed steps,
   16-step windows, --max-seq-len 32768). Request A carries a 100-token prompt
   (inside the window of 128: ONE whole-prompt prefill, the flash kernel with
   the sink on the sliding layers) and keeps decoding through fused windows
   (its context passes 128 while it decodes: the window starts to bite and
   the ring's first pages go out of reach). Request B carries a 3,000-token
   prompt that prefills by 256-token MIXED steps beside A's decode row (12 of
   them: every sliding layer's ring of 25 pages = 400 rows is written over
   about 8 times, every chunk's queries reach back 127 rows into pages an
   earlier chunk wrote), then decodes through the fused 16-step windows at a
   context past 3,000, past one more wrap of the ring. Both ask for logprobs:
   for the first token (the prefill's or the last chunk's logits) and every
   decoded one, the chosen token's log-probability and the five best.
2. `reference`: benchmarks/chip/reference/mimo_v2.py (float32, matmuls at
   "highest", attention as a mask over the full causal scores a block of
   queries at a time, experts as a loop) over each request's whole sequence,
   teacher forced on the tokens the engine gave, on the SAME weights
   dequantized, a layer at a time and the head in blocks of rows so that it
   fits, given the same 16 held experts (routing over all 256) and the same
   19,072 vocabulary rows. In the same sweep over the layers, further passes:
   the residual stream rounded to bfloat16 between layers and nothing else (a
   floor for the program's error); every int8 weight rounded to 4 bits (the
   precision below the one the configuration states: it must NOT pass); and
   the CONTROLS, each a model that differs from the served one in ONE
   mechanism and must NOT pass either: the sink left out; the window taken
   as 127 and as 256; the sliding layers' rotary base taken as the full
   layers'; the rotary on all 192 lanes; attention_value_scale taken as 1;
   the selection bias left out of the pick.

The weights are CONDITIONED as compare_reference.py conditions Kimi-K2's and
compare_reference_laguna_s.py Laguna's, both sides alike, and for their
reasons (PERF.md section 6, PR 27 and PR 36: the loader's random weights as
served make a map no finite-precision program can be compared on):
`SCALE_FIX` on every int8 weight's scales (the bytes dequantize to the
spec's sigma); the embedding at unit rms; the FFNs' output projections
(W_down) at `BRANCH` and attention's (W_o) at `ATTN_BRANCH`, sized on the
sandbox's CPU at the published widths so that an attention branch and an
expert layer each add 0.12-0.18 of a unit residual (a chip holds 16 of 256
experts, of which 4 are picked: an expert layer here is a quarter of a
deployment's, so W_down stays near the spec's sigma where W_o is cut to
0.15); W_q times `QUERY_SOFTEN` (the loader draws W_q and W_k at sigma 1 /
sqrt(head_dim): a normed input gives scores of sigma 21 at 192 lanes, every
softmax an argmax over its keys; at 1/16 the scores have sigma 1.4, a softmax
that weighs its keys e-fold apart but smoothly); the selection bias set as
Kimi-K2's (+1 on the first 4 held experts: every token picks them with a
margin no rounding crosses; -1 on the other 12 held: never picked; 0 on the
240 held elsewhere, which take the other 4 picks by score and only move the
weights' common divisor): the pick is where rounding cannot change it, and
a pick by the scores alone loses the 4 held experts' whole contribution; the
SINKS drawn N(`SINK_MEAN`, 1) a head and layer from the seed (the loader's
are uniform in [-2, 2) and carry 1-3% of a row's mass against 128 keys of
sigma 1.4; at mean 3.5 they carry about an eighth: the record's `sink_mass`
says what they carried), so a softmax without its sink is another model.

Compared: log-softmax of the reference at the engine's positions and token
ids against the engine's log-probabilities, for A and for B. The limits are
in LIMITS below, with their reasons. The record goes to
chiprun_out/compare-mimo-v2-<seed>.json (kept under records/ by the PR that
ran it). Exit 1 if a limit is passed, or if the int4 pass or any control is
not refused by at least one limit.
"""

from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, REPO)
CONFIG = "mimo-v2.5-w8a8-ep16-1chip"
OUT_DIR = os.path.join(REPO, "chiprun_out")

# What may differ between the program and the reference on the same
# weights: the program rounds every matmul's input rows to int8 (one scale
# a token), keeps the residual stream and the cache rows in bf16, runs the
# softmax online from the sink, and sums in another order. The limits are
# compare_reference.py's and Laguna's (Kimi-K2's block on this chip read
# 0.14-0.22 / 0.047-0.051 against int4's 1.2-1.7 / 0.47-0.56; Laguna's 0.17
# / 0.04): each lies between what the program reads and what the nearest
# precision below (int4 weights) and every one-mechanism control read, with
# room on both sides; this configuration's own readings on the chip are
# written beside them in PERF.md section 6 (PR 48) and in the records.
LIMITS = {
    # largest |engine logprob - reference logprob| over every compared entry
    "max_abs_logprob_err": 0.5,
    # root mean square of the same
    "rms_logprob_err": 0.15,
}
SIZES = {
    None: dict(prompt_a=100, decode_a=120, prompt_b=3000, decode_b=440,
               q_block=128, head_rows=4096),
    "cpu": dict(prompt_a=6, decode_a=30, prompt_b=70, decode_b=20,
                q_block=16, head_rows=128),
}
# the one-mechanism controls (reference/mimo_v2.py VARIANTS) that must fail
CONTROLS = ("no_sink", "window_127", "window_256", "one_theta",
            "rotary_all_lanes", "value_scale_1", "no_select_bias")
SCALE_FIX = 3 ** 0.5 / 4.5
EMBED_RMS = 1.0 / 0.02
BRANCH = 0.9
BRANCH_OUT = ("w_down", "moe_w_down")
ATTN_BRANCH = 0.15
QUERY_SOFTEN = 1.0 / 16.0
SINK_MEAN = 3.5


def engine_config(variant):
    from dynamo_tpu.engine.config import EngineConfig

    with open(os.path.join(HERE, "configs", CONFIG + ".json")) as f:
        conf = json.load(f)
    model = os.path.join(HERE, "configs", CONFIG)
    flags = conf["worker_flags"]
    if variant:
        v = conf["variants"][variant]
        model, flags = v["model"], v["worker_flags"]
    opt = {flags[i].lstrip("-").replace("-", "_"): flags[i + 1]
           for i in range(0, len(flags), 2)}
    return model, EngineConfig(
        model=model, quantization=opt.get("quantization", "none"),
        max_seq_len=int(opt["max_seq_len"]),
        num_scheduler_steps=int(opt["num_scheduler_steps"]),
        mixed_batch_tokens=int(opt["mixed_batch_tokens"]),
        max_num_seqs=int(opt["max_num_seqs"]),
        num_pages=int(opt["num_pages"]),
        attention_backend=opt.get("attention_backend", "auto"))



def selection_bias(mcfg):
    """float32 [expert layers, router width]: +1 on the first k/2 held
    experts (a sigmoid score is below 1, so they outrank every unbiased
    expert), -1 on the other held ones (never picked), 0 elsewhere."""
    import numpy as np

    b = np.zeros((mcfg.num_moe_layers, mcfg.num_experts), np.float32)
    lo, held = mcfg.local_expert_offset, mcfg.held_experts
    take = max(mcfg.num_experts_per_tok // 2, 1)
    b[:, lo:lo + take] = 1.0
    b[:, lo + take:lo + held] = -1.0
    return b


def conditioned(params: dict, mcfg, seed: int) -> dict:
    """The same tree with every int8 weight's scales times SCALE_FIX, the
    embedding, the branches' output projections and W_q sized as the module
    docstring says (quantized or not), the selection bias set and the sinks
    drawn from the seed."""
    import numpy as np

    from dynamo_tpu.models.quant import QTensor

    rng = np.random.default_rng(seed)
    out = {}
    for name, w in params.items():
        plain = name.rsplit(".", 1)[-1]
        if plain == "router_bias":
            out[name] = selection_bias(mcfg).astype(np.float32)
            continue
        if plain == "sink":
            out[name] = (SINK_MEAN + rng.standard_normal(w.shape)
                         ).astype(np.float32)
            continue
        c = (EMBED_RMS if plain == "embed" else
             BRANCH if plain in BRANCH_OUT else
             ATTN_BRANCH if plain == "wo" else
             QUERY_SOFTEN if plain == "wq" else 1.0)
        if isinstance(w, QTensor):
            w = type(w)(w.q, w.scale * (SCALE_FIX * c))
        elif c != 1.0:
            w = (w.astype("float32") * c).astype(w.dtype)
        out[name] = w
    return out


def verdict_of(rec: dict) -> dict:
    """What the limits above say of a record's readings (`--judge`)."""
    over = lambda e: any(e[k] > v for k, v in LIMITS.items())  # noqa: E731
    ok = True
    refused = {c: False for c in CONTROLS}
    low = False
    for who in ("A", "B"):
        r = rec["requests"][who]
        ok &= not over(r["program_vs_reference"])
        if r["int4_weights_vs_program"]:
            low |= over(r["int4_weights_vs_program"])
        for c in CONTROLS:
            refused[c] |= over(r["controls_vs_program"][c])
    quantized = rec["requests"]["B"]["int4_weights_vs_program"] is not None
    return {"limits": dict(LIMITS), "program_within_limits": bool(ok),
            "int4_refused": bool(low) if quantized else None,
            "controls_refused": {c: bool(v) for c, v in refused.items()}}


def passes(rec: dict) -> bool:
    return (rec["program_within_limits"] and rec["int4_refused"] is not False
            and all(rec["controls_refused"].values()))


def tokens_for(seed: int, sizes: dict, vocab: int):
    import numpy as np

    rng = np.random.default_rng(seed)
    draw = lambda n: rng.integers(3, vocab, n).tolist()  # noqa: E731
    return draw(sizes["prompt_a"]), draw(sizes["prompt_b"])



def run_engine(args) -> None:
    import dataclasses

    from dynamo_tpu.engine.engine import Engine
    from dynamo_tpu.engine.request import GenRequest
    from dynamo_tpu.ops import attention as att
    from dynamo_tpu.utils.platform import init_backend

    platform = init_backend()
    sizes = SIZES[args.variant]
    _, cfg = engine_config(args.variant)
    seed = args.seed % 2147483647
    eng = Engine(dataclasses.replace(cfg, seed=seed))
    import jax

    shardings = {k: jax.tree.map(lambda a: a.sharding, v)
                 for k, v in eng.params.items()}
    eng.params = {k: jax.device_put(v, shardings[k]) for k, v in conditioned(
        eng.params, eng.model_cfg, seed).items()}
    a, b = tokens_for(args.seed, sizes, eng.model_cfg.vocab_size)
    t0 = time.monotonic()
    eng.add_request(GenRequest("A", a, max_tokens=sizes["decode_a"],
                               temperature=0.0, ignore_eos=True, logprobs=5))
    events, sent_b, steps_mixed = {"A": [], "B": []}, False, 0
    while eng.has_work:
        before = eng.metrics.mixed_count
        for ev in eng.step():
            if ev.token_id >= 0:
                events[ev.request_id].append(ev)
            if ev.request_id == "A" and not sent_b:
                # A decodes: B's prompt now prefills beside A's row
                eng.add_request(GenRequest(
                    "B", b, max_tokens=sizes["decode_b"], temperature=0.0,
                    ignore_eos=True, logprobs=5))
                sent_b = True
        steps_mixed += eng.metrics.mixed_count - before
    stats = eng.metrics.snapshot()
    rec = {
        "platform": platform, "seconds": time.monotonic() - t0,
        "requests": {
            who: {"prompt": p, "tokens": [e.token_id for e in events[who]],
                  "chosen": [e.logprob for e in events[who]],
                  "top": [[list(t) for t in e.top_logprobs]
                          for e in events[who]]}
            for who, p in (("A", a), ("B", b))},
        "mixed_steps": steps_mixed,
        "attention_traced": {f"{op}/{impl}": n for (op, impl), n
                             in att.attention_impl_counts().items()},
        "fallbacks": {f"{op}/{why}": n for (op, why), n
                      in att.pallas_fallback_counts().items()},
        "moe": stats.get("moe"), "attn_kinds": stats.get("attn_kinds"),
        "window_pages_handed_back": eng.win_rings.handed_back,
        "ring_pages": eng.kv_spec.ring_pages,
        "kv_lanes_by_kind": eng.kv_spec.kind_lanes(),
        "kv_pool_shapes": [[list(p.shape) for p in pools]
                           for pools in (eng.k_pages, eng.v_pages)],
    }
    with open(args.scratch, "w") as f:
        json.dump(rec, f)
    print(f"engine: {len(events['A'])} tokens of A, {len(events['B'])} of B "
          f"in {rec['seconds']:.1f}s, {steps_mixed} mixed steps, "
          f"{rec['window_pages_handed_back']} ring pages handed back",
          flush=True)


def run_reference(args) -> None:
    import functools
    import importlib.util

    import jax
    import jax.numpy as jnp
    import numpy as np

    from dynamo_tpu.models import loader
    from dynamo_tpu.models.config import ModelConfig
    from dynamo_tpu.models.quant import QTensor
    from dynamo_tpu.utils.platform import init_backend

    init_backend()
    spec = importlib.util.spec_from_file_location(
        "mimo_v2_reference", os.path.join(HERE, "reference", "mimo_v2.py"))
    ref = importlib.util.module_from_spec(spec)
    sys.modules[spec.name] = ref  # its dataclasses look their module up
    spec.loader.exec_module(ref)

    sizes = SIZES[args.variant]
    with open(args.scratch) as f:
        rec = json.load(f)
    model, ecfg = engine_config(args.variant)
    mcfg = ModelConfig.from_model_name(model)
    if os.path.isdir(model):
        with open(os.path.join(model, "config.json")) as f:
            rc = ref.Config.from_hf(json.load(f))
    else:  # the tiny preset, spelled as the published config spells it
        sys.path.insert(0, os.path.join(REPO, "tests"))
        from mimo_v2_common import hf_dict

        rc = ref.Config.from_hf(hf_dict(mcfg))
    seed = args.seed % 2147483647
    params = conditioned(loader.load_or_init_params(
        mcfg, None, seed=seed, quantization=ecfg.quantization), mcfg, seed)
    quantized = any(isinstance(w, QTensor) for w in params.values())
    share = ref.Share(mcfg.local_expert_offset, mcfg.held_experts)

    @functools.partial(jax.jit, static_argnames="bits")
    def plain(w, bits=8):
        """A leaf as float32, on the device (an int8 weight crosses as
        bytes). bits=4 rounds it to the 4-bit grid first (multiples of
        16): the nearest precision below the configuration's."""
        if not isinstance(w, QTensor):
            return jnp.asarray(w, jnp.float32)
        q = jnp.asarray(w.q, jnp.float32)
        if bits == 4:
            q = jnp.clip(jnp.round(q / 16.0) * 16.0, -112, 112)
        return q * jnp.asarray(w.scale, jnp.float32)

    eps = rc.layernorm_epsilon

    @functools.partial(jax.jit, static_argnames=("kind", "variant"))
    def attend(lp, h, kind, variant):
        with jax.default_matmul_precision("highest"):
            pos = jnp.arange(h.shape[0])
            branch = ref.attention(
                rc, lp, ref.rms_norm(h, lp["attn_norm"], eps), pos, kind,
                sizes["q_block"], variant)
            h = h + branch
            return (h, ref.rms_norm(h, lp["mlp_norm"], eps),
                    jnp.sqrt(jnp.mean(branch * branch)))

    @functools.partial(jax.jit, static_argnames="variant")
    def held_experts(lp, x, variant):
        with jax.default_matmul_precision("highest"):
            return ref.experts(rc, lp, x, share, variant)

    @jax.jit
    def dense_ffn(lp, x):
        with jax.default_matmul_precision("highest"):
            return ref.swiglu(x, lp["w_gate"], lp["w_up"], lp["w_down"])

    @jax.jit
    def head(h, norm, w):
        with jax.default_matmul_precision("highest"):
            return jax.nn.log_softmax(ref.rms_norm(h, norm, eps) @ w, -1)

    @jax.jit
    def sink_mass(lp, h):
        """Mean share of a sliding layer's softmax that its sink carries,
        over the heads and the rows past the window."""
        with jax.default_matmul_precision("highest"):
            pos = jnp.arange(h.shape[0])
            x = ref.rms_norm(h, lp["attn_norm"], eps)
            lanes = int(rc.head_dim * rc.partial_rotary_factor)
            q = ref.rope(jnp.einsum("se,ehd->shd", x, lp["wq"]), pos,
                         rc.swa_rope_theta, lanes)[-64:]
            k = ref.rope(jnp.einsum("se,ekd->skd", x, lp["wk"]), pos,
                         rc.swa_rope_theta, lanes)
            of = jnp.arange(q.shape[1]) // (q.shape[1] // k.shape[1])
            sc = jnp.einsum("qhd,khd->hqk", q, k[:, of]) / rc.head_dim ** 0.5
            qi = pos[-64:][:, None]
            mask = (pos[None] <= qi) & (pos[None] > qi - rc.sliding_window)
            sc = jnp.where(mask[None], sc, -jnp.inf)
            sk = lp["sink"][:, None, None]
            m = jnp.maximum(jnp.max(sc, -1, keepdims=True), sk)
            den = jnp.sum(jnp.exp(sc - m), -1, keepdims=True) + jnp.exp(sk - m)
            return jnp.mean(jnp.exp(sk - m) / den)

    PASSES = {"f32": (8, "model"), "bf16_stream": (8, "model"),
              **{c: (8, c) for c in CONTROLS},
              **({"int4": (4, "model")} if quantized else {})}

    def reference_passes(seq, at):
        """({pass: log-probabilities [len(at), V]}, what the f32 pass's
        branches measured) in one sweep over the layers; a pass's stream
        waits on the host between layers."""
        t0 = time.monotonic()
        hs = {n: np.asarray(plain(params["embed"], bits=b)[jnp.asarray(seq)])
              for n, (b, _) in PASSES.items()}
        seen = {"attn_branch_rms": {}, "ffn_branch_rms": {}, "sink_mass": []}
        for i in range(rc.num_hidden_layers):
            where = ref.layer_index(rc, i)
            kind = rc.kind(i)
            raw = {}
            for n, w in params.items():
                if n in ("embed", "lm_head", "final_norm"):
                    continue
                leaf = n.rsplit(".", 1)[-1]
                if "dense" in where:
                    if n.startswith(ref.DENSE_PREFIX):
                        raw[leaf] = jax.tree.map(
                            lambda a: a[where["dense"]], w)
                elif n.startswith(ref.DENSE_PREFIX):
                    continue
                elif leaf in ref.KIND_LEAVES:
                    if n == ref.KIND_PREFIX[kind] + leaf:
                        raw[leaf] = jax.tree.map(lambda a: a[where["kind"]],
                                                 w)
                else:
                    raw[n] = jax.tree.map(lambda a: a[where[""]], w)
            for bits in sorted({b for b, _ in PASSES.values()},
                               reverse=True):
                lp = {n: plain(jax.device_put(w), bits=bits)
                      for n, w in raw.items()}
                for n in [n for n, (b, _) in PASSES.items() if b == bits]:
                    variant = PASSES[n][1]
                    h = jnp.asarray(hs[n])
                    if n == "bf16_stream":
                        h = h.astype(jnp.bfloat16).astype(jnp.float32)
                    if n == "f32" and kind == ref.SLIDING:
                        seen["sink_mass"].append(float(sink_mass(lp, h)))
                    h, x, rms = attend(lp, h, kind, variant)
                    y = (held_experts(lp, x, variant) if "router" in lp
                         else dense_ffn(lp, x))
                    if n == "f32":
                        seen["attn_branch_rms"].setdefault(kind, []).append(
                            float(rms))
                        seen["ffn_branch_rms"].setdefault(
                            "experts" if "router" in lp else "dense",
                            []).append(float(jnp.sqrt(jnp.mean(y * y))))
                        seen["stream_rms"] = float(jnp.sqrt(jnp.mean(h * h)))
                    hs[n] = np.asarray(h + y)
                del lp
        out = {}
        for n, (b, _) in PASSES.items():
            norm = plain(params["final_norm"], bits=b)
            w = plain(params["lm_head"], bits=b)
            rows = np.asarray(at)
            out[n] = np.concatenate([
                np.asarray(head(jnp.asarray(hs[n][rows[j:j + 256]]), norm, w))
                for j in range(0, len(rows), 256)])
        print(f"reference ({len(PASSES)} passes) over {len(seq)} tokens: "
              f"{time.monotonic() - t0:.1f}s", flush=True)
        return out, seen

    def errors(req, lp, other=None):
        """The engine's entries (or, with `other`, another reference
        pass's at the same entries) against `lp`."""
        d = []
        for i, (tok, chosen, top) in enumerate(
                zip(req["tokens"], req["chosen"], req["top"])):
            if other is not None:
                chosen = other[i, tok]
                top = [(t, other[i, int(t)]) for t, _ in top]
            d.append(chosen - lp[i, tok])
            d.extend(v - lp[i, int(t)] for t, v in top)
        d = np.asarray(d, np.float64)
        per_pos = np.abs(d).reshape(len(req["tokens"]), -1).max(axis=1)
        return {"per_position_max_abs_err": [round(float(v), 4)
                                             for v in per_pos],
                "max_abs_logprob_err": float(np.abs(d).max()),
                "rms_logprob_err": float(np.sqrt((d * d).mean())),
                "entries": int(d.size)}

    out_req = {}
    for who in ("A", "B"):
        req = rec["requests"][who]
        seq = req["prompt"] + req["tokens"][:-1]
        n0 = len(req["prompt"])
        at = [n0 - 1 + i for i in range(len(req["tokens"]))]
        lps, seen = reference_passes(seq, at)
        full = lps["f32"]
        mean = lambda v: float(np.mean(v)) if len(v) else None  # noqa: E731
        out_req[who] = {
            "context": {"prompt_tokens": n0, "decoded": len(req["tokens"]),
                        "last_context": len(seq) + 1},
            "program_vs_reference": errors(req, full),
            "int4_weights_vs_program": (errors(req, lps["int4"])
                                        if quantized else None),
            "controls_vs_program": {c: errors(req, lps[c]) for c in CONTROLS},
            "controls_vs_reference": {c: errors(req, full, lps[c])
                                      for c in CONTROLS},
            "bf16_stream_reference_vs_reference": errors(
                req, full, lps["bf16_stream"]),
            "int4_weights_vs_reference": (errors(req, full, lps["int4"])
                                          if quantized else None),
            "reference_logprob_spread": float(np.std(full)),
            "greedy_token_is_reference_argmax_share": float(np.mean(
                full.argmax(-1) == np.asarray(req["tokens"]))),
            "branches_in_the_f32_pass": {
                "stream_rms_at_the_last_layer": seen.get("stream_rms"),
                "attn_branch_rms": {k: mean(v) for k, v
                                    in seen["attn_branch_rms"].items()},
                "ffn_branch_rms": {k: mean(v) for k, v
                                   in seen["ffn_branch_rms"].items()},
                "sink_mass_on_sliding_layers": mean(seen["sink_mass"])},
        }
    out = {
        "config": CONFIG, "variant": args.variant, "seed": args.seed,
        "device": {"platform": jax.devices()[0].platform,
                   "kind": jax.devices()[0].device_kind},
        "requests": out_req,
        "conditioning": {
            "scale_fix": SCALE_FIX, "embed": EMBED_RMS, "branch": BRANCH,
            "branch_out": BRANCH_OUT, "attn_branch": ATTN_BRANCH,
            "query_soften": QUERY_SOFTEN, "sink_mean": SINK_MEAN,
            "selection_bias": "+1 on the first 4 held, -1 on the other 12"},
        "engine": {k: rec[k] for k in (
            "platform", "seconds", "mixed_steps", "attention_traced",
            "fallbacks", "moe", "attn_kinds", "window_pages_handed_back",
            "ring_pages", "kv_lanes_by_kind", "kv_pool_shapes")},
    }
    out.update(verdict_of(out))
    os.makedirs(OUT_DIR, exist_ok=True)
    tag = f"-{args.variant}" if args.variant else ""
    path = os.path.join(OUT_DIR, f"compare-mimo-v2{tag}-{args.seed}.json")
    with open(path, "w") as f:
        json.dump(out, f, indent=1)
    pick = lambda e: {m: round(e[m], 4) for m in LIMITS}  # noqa: E731
    brief = {who: {
        "program_vs_reference": pick(r["program_vs_reference"]),
        "int4_weights_vs_program": (pick(r["int4_weights_vs_program"])
                                    if quantized else None),
        "bf16_stream_reference_vs_reference": pick(
            r["bf16_stream_reference_vs_reference"]),
        "controls_vs_program": {c: pick(e) for c, e
                                in r["controls_vs_program"].items()},
        "branches": r["branches_in_the_f32_pass"]}
        for who, r in out_req.items()}
    print(json.dumps({"readings": brief, **verdict_of(out)}), flush=True)
    if not passes(out):
        sys.exit(1)


def main() -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--seed", type=int, default=27)
    p.add_argument("--variant", default=None, choices=(None, "cpu"))
    p.add_argument("--phase", default=None, choices=("engine", "reference"))
    p.add_argument("--scratch", default=None)
    p.add_argument("--judge", default=None, metavar="RECORD",
                   help="judge a kept record by the limits written here")
    args = p.parse_args()
    if args.judge:
        with open(args.judge) as f:
            rec = json.load(f)
        rec.update(verdict_of(rec))
        print(json.dumps({k: rec[k] for k in verdict_of(rec)}))
        return 0 if passes(rec) else 1
    if args.phase:
        {"engine": run_engine, "reference": run_reference}[args.phase](args)
        return 0
    os.makedirs(OUT_DIR, exist_ok=True)
    scratch = os.path.join(OUT_DIR, f"compare-mimo-v2-engine-{args.seed}.json")
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu" if args.variant == "cpu" else "tpu,cpu"
    env.pop("XLA_FLAGS", None)
    for phase in ("engine", "reference"):
        cmd = [sys.executable, os.path.abspath(__file__), "--phase", phase,
               "--seed", str(args.seed), "--scratch", scratch]
        if args.variant:
            cmd += ["--variant", args.variant]
        rc = subprocess.run(cmd, env=env, cwd=REPO).returncode
        if rc != 0:
            print(f"compare_reference_mimo_v2.py: phase {phase} exited {rc}",
                  file=sys.stderr)
            return rc
    return 0


if __name__ == "__main__":
    sys.exit(main())
