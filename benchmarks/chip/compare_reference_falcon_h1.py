#!/usr/bin/env python3
"""Falcon-H1-34B's cut on the chip against its float32 reference, at the
cell's own sizes: what the engine's own programs give, logit for logit, on a
~2,000-token prompt fed in 256-token MIXED steps and then decoded through the
fused windows, and on a prompt under 128; and the state a sequence's slot
holds after its prompt against the recurrence's, layer by layer.

    python benchmarks/chip/compare_reference_falcon_h1.py --seed <n> [--variant cpu]

Two child processes, one after the other (a chip belongs to one process):

1. `engine`: the cell's configuration through `dynamo_tpu.engine.Engine` with
   the worker's flags (w8a8, 64 slots = 64 state slots in every layer, 6,144
   pages, 256-token mixed steps, 16-step windows, --max-seq-len 6144).
   Request A carries a ~100-token prompt (one whole-prompt prefill: padding
   rows that may not move its state) and keeps decoding; request B carries a
   ~2,000-token prompt that prefills by 256-token MIXED steps beside A's
   decode row (its state rides its slot of every layer from step to step, its
   keys its pages; the last chunk is padded), then decodes through the fused
   16-step windows. Both ask for logprobs: for the first token and every
   decoded one, the chosen token's log-probability and the five best. When
   B's first token arrives, the ten layers' states S in B's slot are read
   back: what 2,000 tokens accumulated into.
2. `reference`: benchmarks/chip/reference/falcon_h1.py (float32, matmuls at
   "highest", the state-space mixer as the recurrence token by token, every
   multiplier where the published description puts it) over each request's
   whole sequence, teacher forced on the tokens the engine gave, on the SAME
   weights dequantized, a layer at a time and the head in vocabulary blocks
   so that it fits. In the same sweep over the layers, five more passes: the
   residual stream rounded to bfloat16 between layers and nothing else (a
   floor for the program's error), and four CONTROLS that must NOT pass:
   every int8 weight rounded to 4 bits (the precision below the one the
   configuration states); the state S rounded to bfloat16 after every token
   (the precision below the float32 the configuration states for the
   state); `key_multiplier` taken as 1; the attention branch left out.

The weights are CONDITIONED, both sides alike (the loader's random weights as
served make a map no finite-precision program can be compared on: PERF.md
section 6, PR 27). Falcon-H1 is parametrised so that LARGE weights meet SMALL
fixed multipliers (key 0.011, attention_out 0.0375, lm_head 2^-7, ...);
random weights drawn at 1 / sqrt(fan-in) under those multipliers would give
attention scores of sigma 0.01 (a uniform softmax that no control can tell
from another) and an attention branch a hundredth of the others. So each
weight a multiplier scales is conditioned by that multiplier's INVERSE
(`UNDO`: W_k by 1 / key_multiplier, W_in's five runs by 1 / (ssm_in x
ssm_multipliers[i]), W_gate by 1 / mlp_multipliers[0], the embedding, W_o,
W_out, W_down and the head by theirs), which puts q, k, z, x, B, C, dt and
the gate's pre-activation at sigma 1 under the PUBLISHED multipliers, as the
parametrisation intends; then `SCALE_FIX` on every int8 weight's scales, the
embedding at unit rms, and the three branches' output projections sized so
that each adds to the residual stream within a factor of 3 of the others:
W_out and W_down at `BRANCH`, W_o at `ATTN_BRANCH` (an average over ~2,000
keys of sigma-1 values is small: 0.04). The record keeps every layer's three
branch sizes (`branch_rms`). Read on the chip (PR 44, both seeds): in layer 0
attention : Mamba-2 : MLP add 0.19 : 0.15 : 0.09 to a stream of rms 1.0 at
~2,000 tokens of context (B) and 0.55 : 0.15 : 0.09 at ~100 (A): within 3 x
for B, 6 x for A. They do NOT stay so: the stream picks up a component common
to all positions, attention's average over keys stops cancelling, and by
layer 9 attention adds 1.8-1.9 to a stream of 4.3-4.7 where the others still
add 0.15 and 0.09: the faintest branch is a twentieth of the loudest there
(PERF.md section 7). One W_o factor cannot level a branch whose size moves
tenfold with depth and context. A_log and dt_bias stay as the loader draws
them (models/llama.SSM_INITS).

Compared: log-softmax of the reference at the engine's positions and token
ids against the engine's log-probabilities, for A and for B; and B's state
after its prompt, a layer at a time, as ||S - S_ref|| / ||S_ref||, judged on
layer 0 (its input is the embedding alone) over the SLOW_HEADS heads chosen
FROM THE WEIGHTS: the smallest softplus(dt_bias) x exp(A_log), where a
state holds the most tokens and a rounding of the state shows. The limits
are in LIMITS below, with their reasons. The record goes to
chiprun_out/compare-falcon-h1-<seed>.json (kept under records/ by the PR that
ran it). Exit 1 if a limit is passed or a control is not refused by one.
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
CONFIG = "falcon-h1-34b-w8a8-1chip"
OUT_DIR = os.path.join(REPO, "chiprun_out")

# What may differ between the program and the reference on the same
# weights: the program rounds every matmul's input rows to int8 (one scale a
# token), keeps the residual stream, the conv rows and the cache rows in
# bf16, applies a multiplier to a projection's output where the reference
# scales its input, runs the scan in chunks, and sums in another order.
# Every limit lies between two readings on the chip (PERF.md section 6,
# PR 44, and records/pr44-compare-*): the program's largest over its seeds,
# and a control that must NOT pass.
#
# The logprobs: the median over positions of a position's largest error,
# and the rms over every entry (no router here, so no heavy tail: both hold).
# The state: layer 0's SLOW_HEADS slowest heads (chosen from the weights).
# Readings on the chip, seeds 27 and 28 (TPU v5 lite, PR 44): the program
# median 0.032-0.042, rms 0.022-0.027 (the float32 reference with nothing
# but its stream in bf16: 0.007-0.009 / 0.005-0.006); the mildest control,
# int4 weights, median 0.51-0.79, rms 0.30-0.40 (key_multiplier = 1 and no
# attention read 4.9-7.2 on both). The state: the program 0.60-0.62% over
# the 8 slowest heads, a state rounded to bfloat16 after every token
# 2.32-2.38% (over the whole layer 0.86-0.96% against 0.43-0.88%: nothing
# to tell, as PR 42 found; and its logprobs read what the program's read).
# Each limit is the geometric middle of its two readings, so each side has
# the same room: 3.5 x on the median, 3.3 x on the rms, 1.9 x on the state.
LIMITS = {
    # median over positions of the largest |engine logprob - reference
    # logprob| among a position's chosen token and five best
    "median_position_err": 0.15,
    # root mean square over every compared entry
    "rms_logprob_err": 0.09,
    # ||S - S_ref|| / ||S_ref|| of B's state after its prompt, layer 0's
    # SLOW_HEADS slowest heads
    "state_rel_err": 0.012,
}
LOGPROB_LIMITS = ("median_position_err", "rms_logprob_err")
SLOW_HEADS = 8
SIZES = {
    None: dict(prompt_a=100, decode_a=60, prompt_b=2000, decode_b=72,
               q_block=256, vocab_block=32640),
    "cpu": dict(prompt_a=10, decode_a=24, prompt_b=70, decode_b=20,
                q_block=16, vocab_block=128),
}
SCALE_FIX = 3 ** 0.5 / 4.5
BRANCH = 0.15
ATTN_BRANCH = 2.0
# the reference's passes: (bits of an int8 weight, the reference's variant,
# what its configuration changes)
PASSES = {"f32": (8, "model", {}), "bf16_stream": (8, "model", {}),
          "int4": (4, "model", {}), "bf16_state": (8, "bf16_state", {}),
          "key_one": (8, "model", {"key_multiplier": 1.0}),
          "no_attention": (8, "model", {"attention_out_multiplier": 0.0})}
CONTROLS = ("int4", "bf16_state", "key_one", "no_attention")


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


def conditioning(mcfg) -> dict:
    """name -> the factor (a number, or for W_in a vector over its output
    lanes) each weight is conditioned by: the module docstring's UNDO, the
    embedding's unit rms and the branches' sizes."""
    import numpy as np

    m = mcfg.multipliers
    gn = mcfg.mamba_n_groups * mcfg.ssm_state_size
    widths = (mcfg.mamba_d_inner, mcfg.mamba_d_inner, gn, gn,
              mcfg.mamba_num_heads)
    return {
        "embed": 1.0 / (0.02 * m.embedding),
        "lm_head": 1.0 / m.lm_head,
        "wk": 1.0 / m.key,
        "wq": 1.0 / m.attention_in, "wv": 1.0 / m.attention_in,
        "wo": ATTN_BRANCH / m.attention_out,
        "ssm_in": np.concatenate([
            np.full((w,), 1.0 / (m.ssm_in * v), np.float32)
            for w, v in zip(widths, m.ssm)]),
        "ssm_out": BRANCH / m.ssm_out,
        "w_gate": 1.0 / m.mlp[0],
        "w_down": BRANCH / m.mlp[1],
    }


def conditioned(params: dict, mcfg) -> dict:
    """The same tree with every int8 weight's scales times SCALE_FIX and
    each weight times its `conditioning` (quantized or not)."""
    import numpy as np

    from dynamo_tpu.models.quant import QTensor

    by_name = conditioning(mcfg)
    out = {}
    for name, w in params.items():
        c = by_name.get(name, 1.0)
        if isinstance(w, QTensor):
            # W_in's scales are [L, 1, lanes]: the vector rides the lanes
            out[name] = type(w)(w.q, w.scale * (np.float32(SCALE_FIX) * c))
        elif isinstance(c, float) and c == 1.0:
            out[name] = w
        else:
            out[name] = (w.astype("float32") * c).astype(w.dtype)
    return out


def _over(reading: dict, keys=LOGPROB_LIMITS) -> bool:
    return any(reading[k] > LIMITS[k] for k in keys)


def verdict_of(rec: dict) -> dict:
    """What the limits above say of a record's readings (`--judge`)."""
    reqs, state = rec["requests"], rec["state_after_prompt_b"]
    ok = not any(_over(reqs[who]["program_vs_reference"]) for who in "AB")
    first = state["first_layer_slow_heads"]
    ok &= first["program_vs_reference"] <= LIMITS["state_rel_err"]
    refused = {}
    for name in CONTROLS:
        if reqs["B"].get(f"{name}_vs_program") is None:
            refused[name] = None  # float32 weights have no int4 pass
            continue
        by_logprobs = any(_over(reqs[who][f"{name}_vs_program"])
                          for who in "AB")
        by_state = (name == "bf16_state" and first["bf16_state_vs_reference"]
                    > LIMITS["state_rel_err"])
        refused[name] = bool(by_logprobs or by_state)
    return {"limits": dict(LIMITS), "program_within_limits": bool(ok),
            **{f"{name}_refused": refused[name] for name in CONTROLS}}


def passes(rec: dict) -> bool:
    return rec["program_within_limits"] and all(
        rec[f"{name}_refused"] is not False for name in CONTROLS) and all(
        rec[f"{name}_refused"] for name in CONTROLS if name != "int4")


def tokens_for(seed: int, sizes: dict, vocab: int):
    import numpy as np

    rng = np.random.default_rng(seed)
    draw = lambda n: rng.integers(3, vocab, n).tolist()  # noqa: E731
    return draw(sizes["prompt_a"]), draw(sizes["prompt_b"])


def run_engine(args) -> None:
    import dataclasses

    import numpy as np

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
                  for k, v in conditioned(eng.params, eng.model_cfg).items()}
    a, b = tokens_for(args.seed, sizes, eng.model_cfg.vocab_size)
    t0 = time.monotonic()
    eng.add_request(GenRequest("A", a, max_tokens=sizes["decode_a"],
                               temperature=0.0, ignore_eos=True, logprobs=5))
    events, sent_b, steps_mixed, state_b = {"A": [], "B": []}, False, 0, None
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
        if state_b is None and events["B"]:
            # B's last chunk has run and nothing has decoded it yet: its
            # slot holds the state after the prompt, in every layer
            (slot,) = [s for s, q in eng.seqs.items()
                       if q.request_id == "B"]
            (pool,) = eng.k_pages.state  # [layers, slots, H, P, N]
            state_b = np.asarray(pool[:, slot])
            assert len(events["B"]) == 1 and str(state_b.dtype) == "float32"
    stats = eng.metrics.snapshot()
    np.save(args.scratch + ".state.npy", state_b)
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
        "ssm": stats.get("ssm"), "attn_kinds": stats.get("attn_kinds"),
        "admit_blocked": stats.get("admit_blocked"),
        "state_shapes": [list(s.shape) for s in eng.k_pages.state],
        "kv_pool_shape": list(eng.k_pages.pages.shape),
    }
    with open(args.scratch, "w") as f:
        json.dump(rec, f)
    print(f"engine: {len(events['A'])} tokens of A, {len(events['B'])} of B "
          f"in {rec['seconds']:.1f}s, {steps_mixed} mixed steps, "
          f"ssm {rec['ssm']}", flush=True)


def run_reference(args) -> None:
    import dataclasses
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
        "falcon_h1_reference",
        os.path.join(HERE, "reference", "falcon_h1.py"))
    ref = importlib.util.module_from_spec(spec)
    sys.modules[spec.name] = ref  # its dataclasses look their module up
    spec.loader.exec_module(ref)

    sizes = SIZES[args.variant]
    with open(args.scratch) as f:
        rec = json.load(f)
    state_program = np.load(args.scratch + ".state.npy")
    model, ecfg = engine_config(args.variant)
    mcfg = ModelConfig.from_model_name(model)
    if os.path.isdir(model):
        with open(os.path.join(model, "config.json")) as f:
            rc = ref.Config.from_hf(json.load(f))
    else:  # the tiny preset, spelled as the published config spells it
        sys.path.insert(0, os.path.join(REPO, "tests"))
        from falcon_h1_common import hf_dict

        rc = ref.Config.from_hf(hf_dict(mcfg))
    configs = {n: dataclasses.replace(rc, **change)
               for n, (_, _, change) in PASSES.items()}
    params = conditioned(loader.load_or_init_params(
        mcfg, None, seed=args.seed % 2147483647,
        quantization=ecfg.quantization), mcfg)
    quantized = any(isinstance(w, QTensor) for w in params.values())
    todo_passes = {n: v for n, v in PASSES.items()
                   if quantized or n != "int4"}

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

    def rows(w, ids, bits):
        """Rows `ids` of the embedding as float32 (never the whole table)."""
        if isinstance(w, QTensor):
            w = type(w)(np.asarray(w.q)[ids], np.asarray(w.scale)[ids])
        else:
            w = np.asarray(w)[ids]
        return plain(jax.device_put(w), bits=bits)

    @functools.partial(jax.jit, static_argnames=("name", "n0"))
    def one_layer(lp, x, name, n0):
        """The stream after the layer, its three branches' rms, and the
        Mamba-2 state after the first n0 tokens (0: not asked)."""
        with jax.default_matmul_precision("highest"):
            att, ssm, ff, state = ref.branches(
                configs[name], lp, x, sizes["q_block"],
                todo_passes[name][1], n0)
        rms = lambda v: jnp.sqrt(jnp.mean(jnp.square(v)))  # noqa: E731
        return (x + att + ssm + ff, jnp.stack([rms(att), rms(ssm), rms(ff),
                                               rms(x)]),
                state if n0 else jnp.zeros((), jnp.float32))

    @functools.partial(jax.jit, static_argnames="name")
    def head_block(x, norm, w, name):
        c = configs[name]
        with jax.default_matmul_precision("highest"):
            return (ref.rms_norm(x, norm, c.rms_norm_eps) @ w
                    ) * c.lm_head_multiplier

    def reference_passes(seq, at, n0):
        """({pass: log-probabilities [len(at), V]}, {pass: the layers'
        states after n0 tokens [layers, H, P, N]}, the f32 pass's branch
        sizes by layer) in one sweep over the layers; a pass's stream waits
        on the host between layers."""
        t0 = time.monotonic()
        ids = np.asarray(seq)
        hs = {n: np.asarray(rows(params["embed"], ids, b)
                            * configs[n].embedding_multiplier)
              for n, (b, _, _) in todo_passes.items()}
        states = {n: [] for n in todo_passes}
        sizes_by_layer = []
        for i in range(rc.num_hidden_layers):
            raw = {n: jax.tree.map(lambda a: a[i], params[n])
                   for n in ref.LAYER_LEAVES}
            for bits in sorted({b for b, _, _ in todo_passes.values()},
                               reverse=True):
                lp = {n: plain(jax.device_put(w), bits=bits)
                      for n, w in raw.items()}
                for n in [n for n, (b, _, _) in todo_passes.items()
                          if b == bits]:
                    x = jnp.asarray(hs[n])
                    if n == "bf16_stream":
                        x = x.astype(jnp.bfloat16).astype(jnp.float32)
                    x, rms, s = one_layer(lp, x, n, n0)
                    hs[n] = np.asarray(x)
                    if n0:
                        states[n].append(np.asarray(s))
                    if n == "f32":
                        sizes_by_layer.append(
                            [round(float(v), 5) for v in rms])
                del lp
        out = {}
        vb = sizes["vocab_block"]
        head = params["lm_head"]
        vocab = (head.q if isinstance(head, QTensor) else head).shape[-1]
        for n, (b, _, _) in todo_passes.items():
            x = jnp.asarray(hs[n][np.asarray(at)])
            norm = plain(params["final_norm"], bits=b)
            blocks = []
            for v0 in range(0, vocab, vb):
                w = jax.tree.map(lambda a: a[..., v0:v0 + vb], head)
                blocks.append(np.asarray(head_block(
                    x, norm, plain(jax.device_put(w), bits=b), n)))
            logits = np.concatenate(blocks, axis=-1).astype(np.float64)
            logits -= logits.max(axis=-1, keepdims=True)
            out[n] = logits - np.log(np.exp(logits).sum(-1, keepdims=True))
        print(f"reference ({', '.join(todo_passes)}) over {len(seq)} tokens: "
              f"{time.monotonic() - t0:.1f}s", flush=True)
        return (out, {n: np.stack(s) for n, s in states.items() if s},
                sizes_by_layer)

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
                "entries": int(d.size)}

    def rel_err(s, want):
        """Per layer ||s - want|| / ||want||."""
        s, want = (np.asarray(v, np.float64).reshape(len(want), -1)
                   for v in (s, want))
        return [float(np.linalg.norm(a - b) / np.linalg.norm(b))
                for a, b in zip(s, want)]

    # layer 0's slowest heads, FROM THE WEIGHTS: smallest step x decay rate
    rate = (np.log1p(np.exp(np.asarray(params["ssm_dt_bias"][0], np.float64)))
            * np.exp(np.asarray(params["ssm_a_log"][0], np.float64)))
    slow = np.argsort(rate)[:SLOW_HEADS]

    def rel_err_slow(s, want):
        return rel_err([np.asarray(s[0])[slow]], [np.asarray(want[0])[slow]]
                       )[0]

    out_req, state_out = {}, None
    for who in ("A", "B"):
        req = rec["requests"][who]
        seq = req["prompt"] + req["tokens"][:-1]
        n0 = len(req["prompt"])
        at = [n0 - 1 + i for i in range(len(req["tokens"]))]
        lps, states, branch_rms = reference_passes(
            seq, at, n0 if who == "B" else 0)
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
            # by layer: rms of what attention, Mamba-2 and the MLP add, and
            # of the stream they add to (the float32 pass)
            "branch_rms": {"attention_ssm_mlp_stream": branch_rms},
        }
        for name in CONTROLS:
            have = name in lps
            out_req[who][f"{name}_vs_program"] = (
                errors(req, lps[name]) if have else None)
            out_req[who][f"{name}_vs_reference"] = (
                errors(req, full, lps[name]) if have else None)
        if who == "B":
            by_layer = {
                "program_vs_reference": rel_err(state_program, states["f32"]),
                **{f"{n}_vs_reference": rel_err(states[n], states["f32"])
                   for n in states if n != "f32"}}
            state_out = {"by_layer": by_layer,
                         "reference_state_rms": [
                             float(np.sqrt(np.mean(np.square(s))))
                             for s in states["f32"]],
                         "first_layer": {k: v[0]
                                         for k, v in by_layer.items()},
                         "slow_heads": [int(h) for h in slow],
                         "first_layer_slow_heads": {
                             "program_vs_reference": rel_err_slow(
                                 state_program, states["f32"]),
                             **{f"{n}_vs_reference": rel_err_slow(
                                 states[n], states["f32"])
                                for n in states if n != "f32"}}}
    out = {
        "config": CONFIG, "variant": args.variant, "seed": args.seed,
        "device": {"platform": jax.devices()[0].platform,
                   "kind": jax.devices()[0].device_kind},
        "requests": out_req, "state_after_prompt_b": state_out,
        "conditioning": {
            "scale_fix": SCALE_FIX, "branch": BRANCH,
            "attn_branch": ATTN_BRANCH,
            "undo": "each weight a multiplier scales, by its inverse",
            "ssm_vectors": "as the loader draws them (llama.SSM_INITS)"},
        "engine": {k: rec[k] for k in (
            "platform", "seconds", "mixed_steps", "attention_traced",
            "fallbacks", "ssm", "attn_kinds", "admit_blocked",
            "state_shapes", "kv_pool_shape")},
    }
    out.update(verdict_of(out))
    os.makedirs(OUT_DIR, exist_ok=True)
    tag = f"-{args.variant}" if args.variant else ""
    path = os.path.join(OUT_DIR, f"compare-falcon-h1{tag}-{args.seed}.json")
    with open(path, "w") as f:
        json.dump(out, f, indent=1)
    keys = ["program_vs_reference", "bf16_stream_reference_vs_reference"] + [
        f"{n}_vs_program" for n in CONTROLS]
    brief = {who: {k: ({m: r[k][m] for m in LOGPROB_LIMITS} if r[k]
                       else None) for k in keys}
             for who, r in out_req.items()}
    print(json.dumps({"readings": brief,
                      "branch_rms_first_last_layer": {
                          who: [r["branch_rms"]["attention_ssm_mlp_stream"][i]
                                for i in (0, -1)]
                          for who, r in out_req.items()},
                      "state": {k: v for k, v in state_out.items()
                                if k != "by_layer"},
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
    scratch = os.path.join(OUT_DIR,
                           f"compare-falcon-h1-engine-{args.seed}.json")
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
            print(f"compare_reference_falcon_h1.py: phase {phase} exited "
                  f"{rc}", file=sys.stderr)
            return rc
    return 0


if __name__ == "__main__":
    sys.exit(main())
