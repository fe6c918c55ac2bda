#!/usr/bin/env python3
"""LFM2-8B-A1B on the chip against its float32 reference, at the cell's own
flags and sizes, all 24 layers and all 32 experts: what the engine's own
programs give, logit for logit, on a 1,281-token prompt fed by 256-token MIXED
steps and then decoded through the fused windows, and on a prompt under 128.

    python benchmarks/chip/compare_reference_lfm2_moe.py --seed <n> [--variant cpu]

Two child processes, one after the other (a chip belongs to one process):

1. `engine`: the cell's configuration through `dynamo_tpu.engine.Engine` with
   the worker's flags (w8a8, 64 slots = 64 state slots of conv rows, 8,192
   pages, 256-token mixed steps, 16-step windows, --max-seq-len 4096).
   Request A carries a 100-token prompt (one whole-prompt prefill: 28
   padding rows that may not move its two conv rows) and keeps decoding;
   request B carries a 1,281-token prompt that prefills by 256-token MIXED
   steps beside A's decode row (six of them: its two conv rows ride its slot
   across FIVE chunk boundaries in 18 layers, and the last chunk holds ONE
   real row and 255 of padding, so B's first token is computed from the
   slot's two rows and nothing else of the conv's past), then decodes
   through the fused 16-step windows, where the rows are carried on the
   device from step to step. Both ask for logprobs: for the first token (the
   chunk's logits) and every decoded one, the chosen token's log-probability
   and the five best.
2. `reference`: benchmarks/chip/reference/lfm2_moe.py (float32, matmuls at
   "highest", the conv as three shifted products over the whole sequence,
   attention under the full mask, every expert for every token and masked)
   over each request's whole sequence, teacher forced on the tokens the
   engine gave, on the SAME weights dequantized, a layer at a time and a
   layer's 32 experts 8 at a time so that it fits. In the same sweep over
   the layers, the passes of PASSES: the residual stream rounded to bfloat16
   between layers and nothing else (a floor for the program's error); every
   int8 weight rounded to 4 bits (the precision below the one the
   configuration states: it must NOT pass); and the CONTROLS, each a
   mechanism left out, each of which must NOT pass either: the conv's oldest
   tap dropped (K = 2), the B gate left out, the C gate left out, B and C
   swapped (the drawn W_in makes the two runs independent draws, so they
   differ), the q / k norms left out, the selection bias left out of the
   pick, and the conv's state zeroed at every 256-row chunk boundary.

The weights are CONDITIONED as compare_reference.py conditions Kimi-K2's,
both sides alike, and for its reasons (PERF.md section 6, PR 27: the
loader's random weights as served make a map no finite-precision program can
be compared on): `SCALE_FIX` on every int8 weight's scales. This model's
own, with what each is for. **The head is TIED**, so the embedding cannot be
raised to unit rms as the untied models' is: its rows stay as drawn (sigma
0.02, so the logits of a normed row have sigma 0.02 x 2,048^0.5 = 0.9; a
first run at unit rms read logits of sigma 45 and every error 45 times what
is written below: records/pr52-compare-lfm2-moe-27.first-scale.json), and the
branches are sized against THAT stream (`STREAM` = 0.02): the conv operator's
W_out at `CONV_BRANCH` 0.1 x STREAM (a normed input gives B, C, u and the
three taps' sum unit variance, so the operator adds 0.1 of the stream's
rms); the dense FFNs' and the experts' W_down at `FFN_BRANCH` 0.3 x STREAM
(silu(g) u has rms 0.6, a top-4 mixture of independent experts half that:
0.18 and 0.09); attention's W_o at `ATTN_BRANCH` 1.5 x STREAM. The record's
`branch_rms` reports what each adds over the stream's rms in the first and
the last layer of each kind, float32 pass (first run, seed 27: conv 0.10 ->
0.04-0.05, FFN 0.18 -> 0.04-0.05 as the stream grows to 2.4 x its start;
attention 0.17-0.46 -> 0.55-0.66: LOUDER than a factor of 3 over the others
at depth, and left so, PERF.md section 7: at a third of that the q / k
norms' control moves the logits less than the program's int8 activations
do). The q / k norms' weights, which the loader draws at 1, are redrawn
uniform in [0.25, 1.75] (else leaving the norms out would change a head's
scores by the 9% its 64 lanes' rms varies, too faint to refuse) and the
selection bias, which the loader draws at ZERO, normal with sigma
`BIAS_SIGMA` 0.2 (the 4th and 5th of 32 sigmoid scores of logits of sigma
0.9 lie 0.02-0.05 apart, so this bias decides most picks and leaving it out
changes about two of a token's four experts). The router itself is left as
drawn: a pick that flips under rounding swaps a quarter of a layer's routed
output, in the float32 reference under a bf16 stream as in the program,
which is why the logprobs are judged by a median over positions (LIMITS
says how).

Compared: log-softmax of the reference at the engine's positions and token
ids against the engine's log-probabilities, for A and for B; and, apart,
B's FIRST token (position 1,280, the one row of its last chunk), where a
state lost at a chunk boundary shows and nowhere else: every later position
lies two rows or more behind the last boundary, and what the ten rows next
to the five boundaries lose reaches it through six attention layers only.
The limits are in LIMITS below, with their reasons. The record goes to
chiprun_out/compare-lfm2-moe-<seed>.json (kept under records/ by the PR that
ran it). Exit 1 if a limit is passed, or if the int4 pass or a control is
not refused by one.
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
CONFIG = "lfm2-8b-a1b-w8a8-1chip"
OUT_DIR = os.path.join(REPO, "chiprun_out")

# What may differ between the program and the reference on the same
# weights: the program rounds every matmul's input rows to int8 (one scale
# a token), keeps the residual stream, the conv rows and the cache rows in
# bf16, adds 1e-20 where the reference adds 1e-6 to the picked scores' sum
# (reference ASSUMED (h): under 1e-5 relative), and sums in another order.
# Every limit lies between two readings on the chip (PERF.md section 6,
# PR 52, and records/pr52-compare-*): the program's largest over its seeds,
# and the passes that must NOT pass.
#
# The logprobs are judged by the MEDIAN over positions of a position's
# largest error and by the rms over all entries, as the Nemotron-H script
# judges them and for its reason: a router's pick that flips under rounding
# swaps a quarter of a layer's routed output in a few positions, in the
# float32 reference under a bf16 stream as in the program, and a largest
# error anywhere cannot tell that from a wrong mechanism.
#
# B's first token apart (`first_token_err`: the largest error among its
# chosen token and five best): the one compared position whose conv inputs
# come from the slot's two rows alone. Its limit lies between the program's
# reading there and the reading of the pass that zeroes the state at every
# chunk boundary.
#
# The sizes were reckoned BEFORE any limit was written, from the first run
# (seed 27, the embedding at unit rms: logits of sigma 45, every reading
# divided by 45): the program 0.10-0.14 by the median, the smallest control
# 0.38-0.45, the zeroed state 1.1 at B's first token and nothing elsewhere.
# The limits stand between the two readings of the runs at the scale above
# (my chip runs, PR 52, seeds 27 and 28, records/pr52-compare-lfm2-moe-*):
# the program's LARGEST over both seeds and both requests, 0.086 by the
# median, 0.069 by the rms, 0.13 at a first token (B's 0.054 / 0.075), four
# times the float32 reference under a bf16 stream (0.020-0.023 / 0.020-0.030:
# 48 branches deep, each with its input rows rounded to int8); and the
# SMALLEST control, taking of its two requests the larger reading since a
# control is refused if ANY limit is passed on EITHER request: the selection
# bias left out 0.281 / 0.173 (seed 27), B and C swapped 0.367 / 0.218, the
# oldest tap 0.373 / 0.214, the q / k norms 0.413 / 0.251, int4 weights
# 0.669 / 0.459 (seed 28: 0.987 / 0.652), the gates 0.87-1.6 / 0.45-0.94;
# the state zeroed at chunk boundaries 0.996 and 1.706 at B's first token and
# NOTHING elsewhere (A's prompt is one chunk: its readings are the
# program's). So: 0.086 | 0.15 | 0.281, 0.069 | 0.11 | 0.173, 0.13 | 0.4 |
# 0.996, each with 1.6 times of room or more on both sides. (The runs were
# made under a first draft of 0.25 / 0.18 / 0.4, set from the scaled first
# run, which every pass and control met too; the records carry the verdict
# by the limits below, `--judge`.)
LIMITS = {
    # median over positions of the largest |engine logprob - reference
    # logprob| among a position's chosen token and five best
    "median_position_err": 0.15,
    # root mean square over every compared entry
    "rms_logprob_err": 0.11,
    # the same largest error at B's first token alone
    "first_token_err": 0.4,
}
LOGPROB_LIMITS = ("median_position_err", "rms_logprob_err")
CHUNK_ROWS = 256
SIZES = {
    None: dict(prompt_a=100, decode_a=60, prompt_b=5 * CHUNK_ROWS + 1,
               decode_b=72, q_block=256, experts_at_once=8,
               zero_state_every=CHUNK_ROWS),
    "cpu": dict(prompt_a=10, decode_a=24, prompt_b=2 * 64 + 1, decode_b=20,
                q_block=16, experts_at_once=8, zero_state_every=64),
}
SCALE_FIX = 3 ** 0.5 / 4.5
STREAM = 0.02  # the embedding's rows as drawn: the head is tied to them
CONV_BRANCH, FFN_BRANCH, ATTN_BRANCH = 0.1, 0.3, 1.5
BRANCH_OF = {"conv_out": CONV_BRANCH * STREAM, "wo": ATTN_BRANCH * STREAM,
             "dense.w_down": FFN_BRANCH * STREAM,
             "moe_w_down": FFN_BRANCH * STREAM}
QK_NORM_RANGE = (0.25, 1.75)
BIAS_SIGMA = 0.2
# the reference's passes: (bits of an int8 weight, the reference's variant,
# whether the state is zeroed at every chunk boundary)
PASSES = {"f32": (8, "model", False), "bf16_stream": (8, "model", False),
          "int4": (4, "model", False),
          "no_oldest_tap": (8, "no_oldest_tap", False),
          "no_b_gate": (8, "no_b_gate", False),
          "no_c_gate": (8, "no_c_gate", False),
          "swap_bc": (8, "swap_bc", False),
          "no_qk_norm": (8, "no_qk_norm", False),
          "no_select_bias": (8, "no_select_bias", False),
          "zero_state": (8, "model", True)}
CONTROLS = tuple(n for n in PASSES if n not in ("f32", "bf16_stream"))


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


def conditioned(params: dict, seed: int) -> dict:
    """The same tree with every int8 weight's scales times SCALE_FIX, the
    embedding and the branches' output projections sized, and the q / k
    norms' weights and the selection bias redrawn from `seed`, as the
    module docstring says (quantized or not; device or host arrays: an
    int8 weight's values are handed on as they are, its scales alone are
    new, so nothing the size of the weights is held twice)."""
    import numpy as np

    from dynamo_tpu.models.quant import QTensor

    rng = np.random.default_rng(seed + 52)
    out = {}
    for name, w in params.items():
        c = BRANCH_OF.get(name, 1.0)
        if name in ("q_norm", "k_norm"):
            w = rng.uniform(*QK_NORM_RANGE, w.shape).astype(w.dtype)
        elif name == "router_bias":
            w = (BIAS_SIGMA * rng.standard_normal(w.shape)).astype(w.dtype)
        elif isinstance(w, QTensor):
            w = type(w)(w.q, w.scale * (SCALE_FIX * c))
        elif c != 1.0:
            w = (w.astype("float32") * c).astype(w.dtype)
        out[name] = w
    return out


def _over(reading: dict, keys=LOGPROB_LIMITS) -> bool:
    return any(reading[k] > LIMITS[k] for k in keys)


def verdict_of(rec: dict) -> dict:
    """What the limits above say of a record's readings (`--judge`)."""
    reqs = rec["requests"]
    ok = not any(_over(reqs[who]["program_vs_reference"]) for who in "AB")
    ok &= (reqs["B"]["program_vs_reference"]["first_token_err"]
           <= LIMITS["first_token_err"])
    refused = {}
    for name in CONTROLS:
        if reqs["B"].get(f"{name}_vs_program") is None:
            refused[name] = None  # float32 weights have no int4 pass
            continue
        by_logprobs = any(_over(reqs[who][f"{name}_vs_program"])
                          for who in "AB")
        by_first = (reqs["B"][f"{name}_vs_program"]["first_token_err"]
                    > LIMITS["first_token_err"])
        refused[name] = bool(by_logprobs or by_first)
    return {"limits": dict(LIMITS), "program_within_limits": bool(ok),
            "refused": refused}


def passes(rec: dict) -> bool:
    return rec["program_within_limits"] and all(
        v is not False for v in rec["refused"].values())


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
    eng = Engine(dataclasses.replace(cfg, seed=args.seed % 2147483647))
    import jax

    shardings = {k: jax.tree.map(lambda a: a.sharding, v)
                 for k, v in eng.params.items()}
    eng.params = {k: jax.device_put(v, shardings[k])
                  for k, v in conditioned(eng.params, args.seed).items()}
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
        "moe": stats.get("moe"), "conv": stats.get("conv"),
        "ssm": stats.get("ssm"), "attn_kinds": stats.get("attn_kinds"),
        "state_shapes": [list(s.shape) for s in eng.v_pages.state],
        "kv_pool_shape": list(eng.k_pages.pages.shape),
    }
    with open(args.scratch, "w") as f:
        json.dump(rec, f)
    print(f"engine: {len(events['A'])} tokens of A, {len(events['B'])} of B "
          f"in {rec['seconds']:.1f}s, {steps_mixed} mixed steps, "
          f"conv {rec['conv']}", flush=True)


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
        "lfm2_moe_reference", os.path.join(HERE, "reference", "lfm2_moe.py"))
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
        from lfm2_moe_common import hf_dict

        rc = ref.Config.from_hf(hf_dict(mcfg))
    params = conditioned(loader.load_or_init_params(
        mcfg, None, seed=args.seed % 2147483647,
        quantization=ecfg.quantization), args.seed)
    quantized = any(isinstance(w, QTensor) for w in params.values())
    todo_passes = {n: v for n, v in PASSES.items()
                   if quantized or n != "int4"}
    n_exp, at_once = rc.num_experts, sizes["experts_at_once"]
    eps, kd = rc.norm_eps, rc.num_dense_layers
    every = sizes["zero_state_every"]

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

    @functools.partial(jax.jit, static_argnames=("kind", "variant", "zero"))
    def operate(lp, norm, x, kind, variant, zero):
        """(x + operator(norm(x)), rms of what it added over rms of x)."""
        with jax.default_matmul_precision("highest"):
            y = ref.operator(rc, lp, ref.rms_norm(x, norm, eps), kind,
                             sizes["q_block"], variant,
                             every if zero else 0)
            return x + y, jnp.sqrt(jnp.mean(y * y) / jnp.mean(x * x))

    @jax.jit
    def dense_ffn(lp, norm, x):
        with jax.default_matmul_precision("highest"):
            return ref.gated_mlp(ref.rms_norm(x, norm, eps), lp["w_gate"],
                                 lp["w_up"], lp["w_down"])

    @functools.partial(jax.jit, static_argnames=("first", "count", "variant"))
    def some_experts(lp, norm, x, first, count, variant):
        with jax.default_matmul_precision("highest"):
            return ref.experts(rc, lp, ref.rms_norm(x, norm, eps), first,
                               count, variant)

    @jax.jit
    def head(x, norm, embed):
        with jax.default_matmul_precision("highest"):
            return jax.nn.log_softmax(
                ref.rms_norm(x, norm, eps) @ embed.T, -1)  # tied

    EXPERTS = ("moe_w_gate", "moe_w_up", "moe_w_down")
    all_bits = sorted({b for b, _, _ in todo_passes.values()}, reverse=True)

    def put(tree, bits):
        return {n: plain(jax.device_put(w), bits=bits)
                for n, w in tree.items()}

    def reference_passes(seq, at):
        """({pass: log-probabilities [len(at), V]}, {layer: what the
        operator and the FFN added over the stream's rms, float32 pass}) in
        one sweep over the layers; a pass's stream waits on the host
        between layers."""
        t0 = time.monotonic()
        xs = {n: np.asarray(plain(params["embed"], bits=b)[jnp.asarray(seq)])
              for n, (b, _, _) in todo_passes.items()}
        branch = {}
        for i, kind in enumerate(rc.layer_types):
            j = rc.layer_types[:i].count(kind)
            op_raw = {n: jax.tree.map(lambda a: a[j], params[n])
                      for n in ref.STACKS[kind]}
            op_norm, ffn_norm = (jnp.asarray(params[n][i], jnp.float32)
                                 for n in ("operator_norm", "ffn_norm"))
            if i < kd:
                ffn_raw = {n: jax.tree.map(lambda a: a[i],
                                           params[ref.DENSE + n])
                           for n in ref.DENSE_FFN}
            else:
                ffn_raw = {n: jax.tree.map(lambda a: a[i - kd], params[n])
                           for n in ref.EXPERT_FFN}
            for bits in all_bits:
                todo = [n for n, (b, _, _) in todo_passes.items()
                        if b == bits]
                lp = put(op_raw, bits)
                seen = {}
                for n in todo:
                    x = jnp.asarray(xs[n])
                    if n == "bf16_stream":
                        x = x.astype(jnp.bfloat16).astype(jnp.float32)
                    _, variant, zero = todo_passes[n]
                    seen[n], added = operate(lp, op_norm, x, kind, variant,
                                             zero)
                    if n == "f32":
                        branch[i] = {"kind": kind, "operator": float(added)}
                del lp
                if i < kd:
                    lp = put(ffn_raw, bits)
                    ys = {n: np.asarray(dense_ffn(lp, ffn_norm, seen[n]))
                          for n in todo}
                else:
                    small = put({n: w for n, w in ffn_raw.items()
                                 if n not in EXPERTS}, bits)
                    ys = {n: 0.0 for n in todo}
                    for first in range(0, n_exp, at_once):
                        part = dict(small, **put({
                            n: jax.tree.map(
                                lambda a: a[first:first + at_once],
                                ffn_raw[n]) for n in EXPERTS}, bits))
                        for n in todo:
                            ys[n] = ys[n] + np.asarray(some_experts(
                                part, ffn_norm, seen[n], first, at_once,
                                todo_passes[n][1]))
                        del part
                for n in todo:
                    x = np.asarray(seen[n])
                    if n == "f32":
                        branch[i]["ffn"] = float(np.sqrt(
                            np.mean(np.square(ys[n])) / np.mean(x * x)))
                    xs[n] = x + ys[n]
                del seen, ys
        out = {n: np.asarray(head(jnp.asarray(xs[n][np.asarray(at)]),
                                  plain(params["final_norm"], bits=b),
                                  plain(params["embed"], bits=b)))
               for n, (b, _, _) in todo_passes.items()}
        print(f"reference ({', '.join(todo_passes)}) over {len(seq)} tokens: "
              f"{time.monotonic() - t0:.1f}s", flush=True)
        return out, branch

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
                "median_position_err": float(np.median(per_pos)),
                "rms_logprob_err": float(np.sqrt((d * d).mean())),
                "first_token_err": float(per_pos[0]),
                "entries": int(d.size)}

    out_req, branches = {}, {}
    for who in ("A", "B"):
        req = rec["requests"][who]
        seq = req["prompt"] + req["tokens"][:-1]
        n0 = len(req["prompt"])
        at = [n0 - 1 + i for i in range(len(req["tokens"]))]
        lps, branch = reference_passes(seq, at)
        last = len(rc.layer_types) - 1
        first_attn = rc.layer_types.index(ref.ATTENTION)
        last_attn = last - rc.layer_types[::-1].index(ref.ATTENTION)
        branches[who] = {str(i): branch[i] for i in sorted(
            {0, kd, first_attn, last_attn, last})}
        full = lps["f32"]
        out_req[who] = {
            "context": {"prompt_tokens": n0, "decoded": len(req["tokens"]),
                        "last_context": len(seq) + 1},
            "program_vs_reference": errors(req, full),
            "bf16_stream_reference_vs_reference": errors(
                req, full, lps["bf16_stream"]),
            "reference_logprob_spread": float(np.std(full)),
            "greedy_token_is_reference_argmax_share": float(np.mean(
                full.argmax(-1) == np.asarray(req["tokens"]))),
        }
        for name in CONTROLS:
            have = name in lps
            out_req[who][f"{name}_vs_program"] = (
                errors(req, lps[name]) if have else None)
            out_req[who][f"{name}_vs_reference"] = (
                errors(req, full, lps[name]) if have else None)
    out = {
        "config": CONFIG, "variant": args.variant, "seed": args.seed,
        "device": {"platform": jax.devices()[0].platform,
                   "kind": jax.devices()[0].device_kind},
        "requests": out_req, "branch_rms": branches,
        "conditioning": {
            "scale_fix": SCALE_FIX, "stream": STREAM, "branch": BRANCH_OF,
            "qk_norm_weights": f"uniform {list(QK_NORM_RANGE)}",
            "selection_bias_sigma": BIAS_SIGMA},
        "engine": {k: rec[k] for k in (
            "platform", "seconds", "mixed_steps", "attention_traced",
            "fallbacks", "moe", "conv", "ssm", "attn_kinds", "state_shapes",
            "kv_pool_shape")},
    }
    out.update(verdict_of(out))
    os.makedirs(OUT_DIR, exist_ok=True)
    tag = f"-{args.variant}" if args.variant else ""
    path = os.path.join(OUT_DIR, f"compare-lfm2-moe{tag}-{args.seed}.json")
    with open(path, "w") as f:
        json.dump(out, f, indent=1)
    keys = ["program_vs_reference", "bf16_stream_reference_vs_reference"] + [
        f"{n}_vs_program" for n in CONTROLS]
    shown = LOGPROB_LIMITS + ("first_token_err",)
    brief = {who: {k: ({m: round(r[k][m], 4) for m in shown} if r[k]
                       else None) for k in keys}
             for who, r in out_req.items()}
    print(json.dumps({"readings": brief, "branch_rms": branches,
                      **verdict_of(out)}), flush=True)
    if not passes(out):
        sys.exit(1)


def main() -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--seed", type=int, default=27)
    p.add_argument("--variant", default=None, choices=(None, "cpu"))
    p.add_argument("--phase", default=None, choices=("engine", "reference"))
    p.add_argument("--scratch", default=None)
    p.add_argument("--judge", default=None, metavar="RECORD",
                   help="judge a kept record by the limits written here, "
                   "and write the verdict back into it")
    args = p.parse_args()
    if args.judge:
        with open(args.judge) as f:
            rec = json.load(f)
        rec.update(verdict_of(rec))
        with open(args.judge, "w") as f:  # the verdict by the limits here
            json.dump(rec, f, indent=1)
        print(json.dumps({k: rec[k] for k in verdict_of(rec)}))
        return 0 if passes(rec) else 1
    if args.phase:
        {"engine": run_engine, "reference": run_reference}[args.phase](args)
        return 0
    os.makedirs(OUT_DIR, exist_ok=True)
    scratch = os.path.join(OUT_DIR,
                           f"compare-lfm2-moe-engine-{args.seed}.json")
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
            print(f"compare_reference_lfm2_moe.py: phase {phase} exited "
                  f"{rc}", file=sys.stderr)
            return rc
    return 0


if __name__ == "__main__":
    sys.exit(main())
