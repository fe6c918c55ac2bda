#!/usr/bin/env python3
"""Kimi-K2 share on the chip against its float32 reference, at the cell's
own context: what the engine's own programs give, logit for logit.

    python benchmarks/chip/compare_reference.py --seed <n> [--variant cpu]

Two child processes, one after the other (a chip belongs to one process):

1. `engine`: the cell's configuration through `dynamo_tpu.engine.Engine` with
   the worker's flags (w8a8, 64 slots, 4,096 pages, 256-token mixed steps,
   prefix cache on, the configuration's --max-seq-len). Request A carries the
   cell's shared prefix (4,096 tokens since the cell took ISSUE 27's
   fallback; the records of PR 27 were made at 8,192 and 10,240 positions,
   the longer context, and not made again) and keeps decoding; request B carries the same prefix and a tail of
   its own, so its prefix is served from cached pages and its tail prefills
   by 256-token MIXED steps beside A's decode row; then B decodes through the
   fused 16-step window over the paged cache. B asks for logprobs: for its
   first token (the chunk program's logits) and every decoded one, the
   chosen token's log-probability and the five best.
2. `reference`: benchmarks/chip/reference/kimi_k2.py (float32, matmuls at
   "highest", expanded MLA, experts as a loop) over B's whole sequence, teacher
   forced on the tokens the engine gave, on the SAME weights dequantized
   (loader.random_quantized_params from the seed), a layer at a time and the
   attention a block of queries at a time so that it fits. In the same
   sweep over the layers, twice more: with nothing but the residual stream
   rounded to bfloat16 between layers (the least of the program's
   departures, alone: a floor for its error), and with every int8 weight
   rounded to 4 bits: the precision below the one the configuration
   states, which must NOT pass.

The weights are CONDITIONED for this comparison, both sides alike, because
the loader's random weights as the cell serves them make a map that no
finite-precision program can be compared on. The readings are PERF.md's
(section 6, PR 27; records/pr27-reference-sensitivity-cpu.json): the
float32 reference ALONE, at these widths, with only its residual stream
rounded to bfloat16 between layers, ends 39% away from itself in the final
hidden state and 1.85 nats rms in the log-probabilities (spread 4.5); a
disturbance of 0.1% a layer grows to 35%. Four things make it so, and each is
set to what a trained model has:

- `SCALE_FIX`: the loader's int8 weights are uniform bytes with a step of
  4.5 sigma / 127, so they dequantize to 2.6 x the sigma their shapes call
  for, attention scores spread by 8 where a trained model's spread by 1-2,
  and a softmax over 8k keys turns on one or two of them. With the step at
  sqrt(3) sigma / 127 the same bytes dequantize to the spec's sigma.
- `EMBED_RMS`: the embedding's sigma is 0.02, so the first layer's output IS
  the stream and every later layer adds as much again: nothing is a
  residual. The embedding is brought to unit rms.
- `BRANCH`: attention's W_o and the FFNs' W_down at 0.15 of their sigma: a
  branch adds 15-20% of the stream, as in a trained model's middle layers,
  and a disturbance is carried on, not multiplied (1% a layer then ends at
  5.4% where it ended past 22% with the first fix alone).
- `selection_bias`: with the zero bias of a fresh router the 8th and 9th of
  a token's 384 scores lie closer together than int8 rounding moves them,
  the pick flips in a large part of the token-layers, and a held expert's
  whole contribution appears or vanishes. Like comparing logits and not
  sampled tokens: compare where rounding cannot change a discrete choice.
  Four of the held experts are picked by every token with a margin and
  twenty by none; the other four picks go, by score, to experts held
  elsewhere, whose flips only move the weights' common divisor. Scores,
  bias-based selection, normalisation, scaling and the grouped matmuls are
  all still compared.

The int8 BYTES, the shapes, the programs and the kernels are the cell's own;
only scales and one bias differ from what it serves, and a step's time does
not depend on either.

Compared: log-softmax of the reference at the engine's positions and token
ids against the engine's log-probabilities. The limits are in LIMITS below,
with their reasons. The record goes to chiprun_out/compare-kimi-k2-<seed>.json
(kept under records/ by the PR that ran it). Exit 1 if a limit is passed, or
if the int4 run is not refused by one.
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
CONFIG = "kimi-k2-w8a8-ep16-1chip"
OUT_DIR = os.path.join(REPO, "chiprun_out")

# What may differ between the program and the reference on the same
# weights: the program rounds every matmul's input rows to int8 (one scale
# a token), keeps the residual stream, the cache rows and W_UK / W_UV
# products in bf16 (8 bits of mantissa), and sums in another order. Each
# limit lies between two READINGS on the chip at the cell's context (v5e,
# seeds 27 and 28, PR 27; records/pr27-compare-kimi-k2-*.residual-
# conditioned.json), about 2.3 x above the first and 2.4-3.2 x below the
# second: the largest the program gave (0.224 / 0.0507; the bf16 stream
# alone gives 0.037 / 0.0146) and the smallest that weights rounded to 4
# bits gave (1.197 / 0.474). The log-probabilities spread by 1.7.
LIMITS = {
    # largest |engine logprob - reference logprob| over every compared entry
    "max_abs_logprob_err": 0.5,
    # root mean square of the same
    "rms_logprob_err": 0.15,
}
SIZES = {
    None: dict(prefix=4096, tail_a=40, tail_b=300, decode=33, q_block=256),
    "cpu": dict(prefix=64, tail_a=8, tail_b=24, decode=20, q_block=16),
}


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


# uniform bytes spread by 127 / sqrt(3) steps; the loader's step is
# 4.5 sigma / 127 (models/loader.random_quantized_params)
SCALE_FIX = 3 ** 0.5 / 4.5
# on top of it: the embedding at unit rms (its sigma is 0.02), and what a
# branch adds to the residual stream (attention's W_o, the FFNs' W_down) at
# BRANCH of what the specs' sigmas make it
EMBED_RMS = 1.0 / 0.02
BRANCH = 0.15
BRANCH_OUT = ("wo", "w_down", "moe_w_down")


def conditioned(params: dict) -> dict:
    """The same tree with every int8 weight's scales times SCALE_FIX, the
    embedding and the branches' output projections sized as the module
    docstring says (quantized or not). The router's bias is set apart."""
    from dynamo_tpu.models.quant import QTensor

    out = {}
    for name, w in params.items():
        plain = name.rsplit(".", 1)[-1]
        c = (EMBED_RMS if plain == "embed" else
             BRANCH if plain in BRANCH_OUT else 1.0)
        if isinstance(w, QTensor):
            w = type(w)(w.q, w.scale * (SCALE_FIX * c))
        elif c != 1.0:
            w = (w.astype("float32") * c).astype(w.dtype)
        out[name] = w
    return out


def selection_bias(mcfg):
    """float32 [expert layers, router width]: +1 on the first k/2 held
    experts (a sigmoid score is below 1, so they outrank every unbiased
    expert), -1 on the other held ones (never picked), 0 elsewhere."""
    import numpy as np

    b = np.zeros((mcfg.num_moe_layers, mcfg.num_experts), np.float32)
    lo, held = mcfg.local_expert_offset, mcfg.held_experts
    take = mcfg.num_experts_per_tok // 2
    b[:, lo:lo + take] = 1.0
    b[:, lo + take:lo + held] = -1.0
    return b


def tokens_for(seed: int, sizes: dict, vocab: int):
    import numpy as np

    rng = np.random.default_rng(seed)
    draw = lambda n: rng.integers(3, vocab, n).tolist()  # noqa: E731
    prefix = draw(sizes["prefix"])
    return prefix + draw(sizes["tail_a"]), prefix + draw(sizes["tail_b"])


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
    bias = eng.params["router_bias"]
    eng.params = conditioned(eng.params)
    eng.params["router_bias"] = jax.device_put(
        selection_bias(eng.model_cfg).astype(bias.dtype), bias.sharding)
    a, b = tokens_for(args.seed, sizes, eng.model_cfg.vocab_size)
    t0 = time.monotonic()
    eng.add_request(GenRequest("A", a, max_tokens=sizes["decode"] + 200,
                               temperature=0.0, ignore_eos=True))
    events, sent_b, steps_mixed = [], False, 0
    while eng.has_work:
        before = eng.metrics.mixed_count
        for ev in eng.step():
            if ev.request_id == "B" and ev.token_id >= 0:
                events.append(ev)
            if ev.request_id == "A" and not sent_b:
                # A decodes: B's tail now prefills beside A's row
                eng.add_request(GenRequest(
                    "B", b, max_tokens=sizes["decode"], temperature=0.0,
                    ignore_eos=True, logprobs=5))
                sent_b = True
        steps_mixed += eng.metrics.mixed_count - before
        if events and events[-1].finished:
            eng.abort_request("A")
    stats = eng.metrics.snapshot()
    pc = eng.prefix_cache.stats()
    rec = {
        "platform": platform, "seconds": time.monotonic() - t0,
        "prompt": b, "tokens": [e.token_id for e in events],
        "chosen": [e.logprob for e in events],
        "top": [[list(t) for t in e.top_logprobs] for e in events],
        "mixed_steps": steps_mixed, "prefix_cache": pc,
        "cached_tokens_served": pc["cached_tokens_served"],
        "attention_traced": {f"{op}/{impl}": n for (op, impl), n
                             in att.attention_impl_counts().items()},
        "fallbacks": {f"{op}/{why}": n for (op, why), n
                      in att.pallas_fallback_counts().items()},
        "moe": stats.get("moe"), "attn": stats.get("attn"),
        "kv_pool_shapes": [list(eng.k_pages.shape), list(eng.v_pages.shape)],
    }
    with open(args.scratch, "w") as f:
        json.dump(rec, f)
    print(f"engine: {len(events)} tokens of B in {rec['seconds']:.1f}s, "
          f"{steps_mixed} mixed steps, cached tokens served "
          f"{pc['cached_tokens_served']}", flush=True)


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
        "kimi_k2_reference", os.path.join(HERE, "reference", "kimi_k2.py"))
    ref = importlib.util.module_from_spec(spec)
    sys.modules[spec.name] = ref  # its dataclasses look their module up
    spec.loader.exec_module(ref)

    sizes = SIZES[args.variant]
    with open(args.scratch) as f:
        rec = json.load(f)
    model, ecfg = engine_config(args.variant)
    mcfg = ModelConfig.from_model_name(model)
    params = loader.load_or_init_params(
        mcfg, None, seed=args.seed % 2147483647,
        quantization=ecfg.quantization)
    params = conditioned(params)
    params["router_bias"] = selection_bias(mcfg)
    f_, bf, bs, orig, ms, msad, _ = mcfg.rope_yarn_scaling
    rc = ref.Config(
        hidden_size=mcfg.hidden_size, num_hidden_layers=mcfg.num_layers,
        num_attention_heads=mcfg.num_heads, q_lora_rank=mcfg.q_lora_rank,
        kv_lora_rank=mcfg.kv_lora_rank,
        qk_nope_head_dim=mcfg.qk_nope_head_dim,
        qk_rope_head_dim=mcfg.qk_rope_head_dim, v_head_dim=mcfg.v_head_dim,
        n_routed_experts=mcfg.num_experts,
        num_experts_per_tok=mcfg.num_experts_per_tok,
        n_shared_experts=mcfg.num_shared_experts,
        first_k_dense_replace=mcfg.first_k_dense,
        routed_scaling_factor=mcfg.routed_scaling_factor,
        norm_topk_prob=mcfg.norm_topk_prob, rms_norm_eps=mcfg.rms_norm_eps,
        rope_theta=mcfg.rope_theta,
        rope_scaling={"factor": f_, "beta_fast": bf, "beta_slow": bs,
                      "mscale": ms, "mscale_all_dim": msad,
                      "original_max_position_embeddings": orig})
    share = (ref.Share(mcfg.local_expert_offset, mcfg.held_experts)
             if mcfg.num_local_experts else None)
    seq = rec["prompt"] + rec["tokens"][:-1]
    n0 = len(rec["prompt"])
    at = [n0 - 1 + i for i in range(len(rec["tokens"]))]
    positions = jnp.arange(len(seq))

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

    @jax.jit
    def one_layer(lp, h):
        with jax.default_matmul_precision("highest"):
            return ref.layer(rc, lp, h, positions, share, sizes["q_block"])

    @jax.jit
    def head(h, norm, w):
        with jax.default_matmul_precision("highest"):
            h = ref.rms_norm(h[jnp.asarray(at)], norm, rc.rms_norm_eps)
            return jax.nn.log_softmax(h @ w, -1)

    def reference_passes(quantized):
        """{pass: log-probabilities [positions, V]} in one sweep over the
        layers: `f32` the reference; `bf16_stream` the same weights with the
        residual stream rounded to bfloat16 between layers and nothing else
        (the smallest of the program's departures, alone); `int4` every
        int8 weight rounded to 4 bits."""
        t0 = time.monotonic()
        bits = {"f32": 8, "bf16_stream": 8, **({"int4": 4} if quantized
                                                else {})}
        hs = {n: plain(params["embed"], bits=b)[jnp.asarray(seq)]
              for n, b in bits.items()}
        k = rc.first_k_dense_replace
        for i in range(rc.num_hidden_layers):
            pre, j = (ref.DENSE_PREFIX, i) if i < k else ("", i - k)
            raw = {n[len(pre):]: jax.device_put(
                       jax.tree.map(lambda a: a[j], w))
                   for n, w in params.items()
                   if n.startswith(pre) and (pre or "." not in n)
                   and n not in ("embed", "lm_head", "final_norm")}
            for b in sorted(set(bits.values()), reverse=True):
                lp = {n: plain(w, bits=b) for n, w in raw.items()}
                for n in [n for n in bits if bits[n] == b]:
                    h = hs[n]
                    if n == "bf16_stream":
                        h = h.astype(jnp.bfloat16).astype(jnp.float32)
                    hs[n] = one_layer(lp, h)
                del lp
        out = {n: np.asarray(head(hs[n], plain(params["final_norm"], bits=b),
                                  plain(params["lm_head"], bits=b)))
               for n, b in bits.items()}
        print(f"reference ({', '.join(bits)}): "
              f"{time.monotonic() - t0:.1f}s", flush=True)
        return out

    def errors(lp, other=None):
        """The engine's entries (or, with `other`, another reference
        pass's at the same entries) against `lp`."""
        d = []
        for i, (tok, chosen, top) in enumerate(
                zip(rec["tokens"], rec["chosen"], rec["top"])):
            if other is not None:
                chosen = other[i, tok]
                top = [(t, other[i, int(t)]) for t, _ in top]
            d.append(chosen - lp[i, tok])
            d.extend(v - lp[i, int(t)] for t, v in top)
        d = np.asarray(d, np.float64)
        per_pos = np.abs(d).reshape(len(rec["tokens"]), -1).max(axis=1)
        return {"per_position_max_abs_err": [round(float(v), 4)
                                             for v in per_pos],
                "max_abs_logprob_err": float(np.abs(d).max()),
                "rms_logprob_err": float(np.sqrt((d * d).mean())),
                "entries": int(d.size)}

    quantized = any(isinstance(w, QTensor) for w in params.values())
    # a rehearsal without int8 weights has no precision below to try
    lps = reference_passes(quantized)
    full = lps["f32"]
    got = errors(full)
    low = errors(lps["int4"]) if quantized else None
    rounded = errors(full, lps["bf16_stream"])
    agree = float(np.mean(full.argmax(-1) == np.asarray(rec["tokens"])))
    verdict = {
        "program_within_limits": all(got[k] <= v for k, v in LIMITS.items()),
        "int4_refused": (any(low[k] > v for k, v in LIMITS.items())
                         if quantized else None),
    }
    out = {
        "config": CONFIG, "variant": args.variant, "seed": args.seed,
        "device": {"platform": jax.devices()[0].platform,
                   "kind": jax.devices()[0].device_kind},
        "context": {"prompt_tokens": n0, "shared_prefix": sizes["prefix"],
                    "decoded": len(rec["tokens"]),
                    "cached_tokens_served": rec["cached_tokens_served"],
                    "mixed_steps": rec["mixed_steps"]},
        "limits": LIMITS, "program_vs_reference": got,
        "int4_weights_vs_program": low,
        "bf16_stream_reference_vs_reference": rounded,
        "conditioning": {
            "scale_fix": SCALE_FIX, "embed": EMBED_RMS, "branch": BRANCH,
            "branch_out": BRANCH_OUT, "selection_bias": True},
        "reference_logprob_spread": float(np.std(full)),
        "greedy_token_is_reference_argmax_share": agree,
        "engine": {k: rec[k] for k in (
            "platform", "seconds", "attention_traced", "fallbacks", "moe",
            "attn", "kv_pool_shapes", "prefix_cache")},
        **verdict,
    }
    os.makedirs(OUT_DIR, exist_ok=True)
    tag = f"-{args.variant}" if args.variant else ""
    path = os.path.join(OUT_DIR, f"compare-kimi-k2{tag}-{args.seed}.json")
    with open(path, "w") as f:
        json.dump(out, f, indent=1)
    print(json.dumps({k: out[k] for k in (
        "program_vs_reference", "int4_weights_vs_program",
        "bf16_stream_reference_vs_reference", "limits",
        "program_within_limits", "int4_refused", "context")}), flush=True)
    if not verdict["program_within_limits"] or (
            verdict["int4_refused"] is False):
        sys.exit(1)


def main() -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--seed", type=int, default=27)
    p.add_argument("--variant", default=None, choices=(None, "cpu"))
    p.add_argument("--phase", default=None, choices=("engine", "reference"))
    p.add_argument("--scratch", default=None)
    args = p.parse_args()
    if args.phase:
        {"engine": run_engine, "reference": run_reference}[args.phase](args)
        return 0
    os.makedirs(OUT_DIR, exist_ok=True)
    scratch = os.path.join(OUT_DIR, f"compare-kimi-k2-engine-{args.seed}.json")
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
            print(f"compare_reference.py: phase {phase} exited {rc}",
                  file=sys.stderr)
            return rc
    return 0


if __name__ == "__main__":
    sys.exit(main())
