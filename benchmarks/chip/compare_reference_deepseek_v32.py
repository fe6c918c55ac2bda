#!/usr/bin/env python3
"""DeepSeek-V3.2-Exp's share on the chip against its float32 reference, at the
cell's own context: what the engine's own programs give, logit for logit,
and which rows its selection kept.

    python benchmarks/chip/compare_reference_deepseek_v32.py --seed <n> [--variant cpu]

Two child processes, one after the other (a chip belongs to one process):

1. `engine`: the cell's configuration through `dynamo_tpu.engine.Engine` with
   the worker's flags (w8a8, 64 slots, 8,192 pages, 256-token mixed steps,
   prefix cache on, --max-seq-len 32768), under the cell's traffic shape.
   Request A carries the 28,672-token document and keeps decoding; request B
   carries the same document and a question of its own, so its document is
   served from cached pages and its question prefills by 256-token MIXED steps
   beside A's decode row (each query selecting its own 2,048 rows); then B
   decodes through the fused 16-step window at a context past 28.9k, every
   token of every layer scoring its index keys and attending over the 2,048
   rows it selects. B asks for logprobs: for its first token (the chunk's
   logits) and every decoded one, the chosen token's log-probability and the
   five best. The selected rows of B's decode steps are read out of the same
   programs through `ops/attention.DSA_TAP` (a `jax.debug.callback` on what
   `jax.lax.top_k` returned: the programs are the timed ones plus that
   callback, which is why the benchmark itself never sets it).
2. `reference`: benchmarks/chip/reference/deepseek_v32.py (float32, matmuls at
   "highest", expanded MLA, the selection as a mask over full causal scores,
   experts as a loop) over B's whole sequence, teacher forced on the tokens
   the engine gave, on the SAME weights dequantized, a layer at a time and
   the attention a block of queries at a time so that it fits. In the same
   sweep over the layers, three more passes: the residual stream rounded to
   bfloat16 between layers and nothing else (a floor for the program's
   error); every int8 weight rounded to 4 bits (the precision below the one
   the configuration states: it must NOT pass); and the CONTROL whose
   selection is the last 2,048 positions (it must NOT pass either).

The weights are CONDITIONED as compare_reference.py conditions Kimi-K2's, both
sides alike, and for its reasons (PERF.md section 6, PR 27: the loader's
random weights as served make a map no finite-precision program can be
compared on): `SCALE_FIX` on every int8 weight's scales (the indexer's two
projections among them), the embedding at unit rms, the FFNs' output
projections at `BRANCH` and attention's at `ATTN_BRANCH` (smaller: the
selection multiplies a disturbance where values are random, see there), and
a selection bias that takes the router's
discrete choice out of rounding's reach: +1 on four of the 16 held experts
(all in group 0, which is then always among the 4 kept groups), -1 on the
other twelve. The indexer's discrete choice is NOT conditioned away: it is
what this comparison is for. Its flips (a score within rounding of the
2,048th) are reported as the sets' overlap, a layer at a time, and bounded.

Compared: log-softmax of the reference at the engine's positions and token
ids against the engine's log-probabilities, and the engine's selected sets
against the reference's. The limits are in LIMITS below, with their reasons.
The record goes to chiprun_out/compare-deepseek-v32-<seed>.json (kept under
records/ by the PR that ran it). Exit 1 if a limit is passed, or if the int4
pass or the recency control is not refused by one.
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
CONFIG = "deepseek-v32-w8a8-ep16-1chip"
OUT_DIR = os.path.join(REPO, "chiprun_out")

# What may differ between the program and the reference on the same
# weights: the program rounds every matmul's input rows to int8 (one scale
# a token), keeps the residual stream, the cache rows (latent and index key),
# the indexer's queries and W_UK / W_UV products in bf16, sums in another
# order, and so can pick another row where two index scores lie within
# rounding of the 2,048th. The first two limits are compare_reference.py's
# (Kimi-K2's block on this chip read 0.14-0.22 / 0.047-0.051 against int4's
# 1.2-1.7 / 0.47-0.56). This configuration's readings on the chip (seeds 27 /
# 28, PR 32, records/pr32-compare-deepseek-v32-*.json): the program 0.124 /
# 0.158 and rms 0.046 / 0.047 (the float32 reference with its stream rounded
# to bf16 and nothing else: 0.072 / 0.082, rms 0.026); int4 weights 0.89 /
# 0.78, rms 0.32 / 0.29; a selection by recency 0.54 / 0.60, rms 0.23 / 0.22.
LIMITS = {
    # largest |engine logprob - reference logprob| over every compared entry
    "max_abs_logprob_err": 0.5,
    # root mean square of the same
    "rms_logprob_err": 0.15,
}
# The share of the reference's selected rows that the program selected too,
# the mean over B's decode positions, a layer at a time. Layer 0 sees the
# SAME input on both sides (the embedding row), so its overlap is the
# indexer's own arithmetic and nothing else: 0.990 on the chip whatever the
# conditioning (seeds 27 / 28, PR 32), against a selection by recency's
# index_topk / context = 0.07. Deeper layers add what the stream has
# gathered by then (int8 rounding in the FFNs, the flips before), so their
# limit stands lower (read on the chip: 0.99 falling to 0.961 / 0.963 at
# layer 8); a wrong selection reads like recency's 0.07 there too.
MIN_OVERLAP_FIRST_LAYER = 0.97
MIN_OVERLAP_ANY_LAYER = 0.85
SIZES = {
    None: dict(prefix=28672, tail_a=40, tail_b=300, decode=33, q_block=64,
               a_extra=200),
    "cpu": dict(prefix=64, tail_a=8, tail_b=72, decode=20, q_block=16,
                a_extra=40),
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
BRANCH_OUT = ("w_down", "moe_w_down")
# attention's W_o: 0.02 of its sigma, not the FFNs' 0.15. Under the selection
# a random model multiplies a disturbance through attention as Kimi-K2's
# block does not: where every selected row carries about the same weight and
# the values are random, the output is a random walk's end, and replacing 1%
# of its 2,048 terms (what bf16 rounding of the index scores flips at layer
# 0) moves it by sqrt(2 x 1%) = 14%. At 0.15 that is 2% of the stream, which
# flips more picks in the next layer: on the chip (seeds 27 / 28, PR 32,
# records/pr32-compare-*.attn-branch-0.15.json) the sets' overlap fell 0.99
# -> 0.45 over the 9 layers and the float32 reference ALONE, its stream
# rounded to bf16 between layers, ended rms 0.74-0.83 from itself, the
# program 1.08, int4 1.55-1.71, recency 2.3-2.5: nothing to set a limit
# between. A trained indexer is distilled from the attention's own
# distribution, so the rows at its threshold carry no weight; random
# weights cannot have that, but a smaller branch carries the disturbance on
# without multiplying it (on the CPU at a small size, 2,048 tokens keeping
# 128: the bf16-stream floor 0.069 -> 0.005 rms from 0.15 to 0.02 while the
# recency control only fell 0.227 -> 0.057). On the chip at 0.02 (the
# readings beside LIMITS): the floor 0.026, the program 0.046, recency 0.22.
ATTN_BRANCH = 0.02


def conditioned(params: dict) -> dict:
    """The same tree with every int8 weight's scales times SCALE_FIX, the
    embedding and the branches' output projections sized as the module
    docstring says (quantized or not). The router's bias is set apart."""
    from dynamo_tpu.models.quant import QTensor

    out = {}
    for name, w in params.items():
        plain = name.rsplit(".", 1)[-1]
        c = (EMBED_RMS if plain == "embed" else
             ATTN_BRANCH if plain == "wo" else
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


def verdict_of(rec: dict) -> dict:
    """What the limits above say of a record's readings (the record's own
    readings and nothing else, so that a kept record can be judged again:
    `--judge <record>`)."""
    sets = rec["selected_sets_overlap_by_layer"]
    over = lambda e: any(e[k] > v for k, v in LIMITS.items())  # noqa: E731
    low = rec["int4_weights_vs_program"]
    return {
        "limits": dict(LIMITS,
                       min_overlap_first_layer=MIN_OVERLAP_FIRST_LAYER,
                       min_overlap_any_layer=MIN_OVERLAP_ANY_LAYER),
        "selected_sets_worst_layer_mean_overlap": min(
            x["program_mean"] for x in sets),
        "program_within_limits": (
            not over(rec["program_vs_reference"])
            and sets[0]["program_mean"] >= MIN_OVERLAP_FIRST_LAYER
            and all(x["program_mean"] >= MIN_OVERLAP_ANY_LAYER
                    for x in sets)),
        "int4_refused": over(low) if low else None,
        "recency_refused": (
            over(rec["recency_selection_vs_program"])
            or any(x["recency_mean"] < MIN_OVERLAP_ANY_LAYER for x in sets)),
        "recency_refused_by_logprobs": over(
            rec["recency_selection_vs_program"]),
    }


def passes(rec: dict) -> bool:
    return (rec["program_within_limits"] and rec["int4_refused"] is not False
            and rec["recency_refused"])


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
    # B's decode rows sit at these positions (A's stay below them): their
    # selected rows, a layer after another, a step after another
    n0 = sizes["prefix"] + sizes["tail_b"]
    wanted = range(n0, n0 + sizes["decode"] - 1)
    # ... and A never reaches them: a position names one sequence's row
    assert (sizes["prefix"] + sizes["tail_a"] + sizes["decode"]
            + sizes["a_extra"]) < n0
    picked = {}

    def tap(kind, qpos, sel, valid):
        if kind != "decode":
            return
        qpos = np.asarray(qpos)
        for row in np.flatnonzero((qpos >= wanted[0]) & (qpos <= wanted[-1])):
            picked.setdefault(int(qpos[row]), []).append(
                np.asarray(sel[row])[np.asarray(valid[row])].tolist())

    att.DSA_TAP = tap
    eng = Engine(dataclasses.replace(cfg, seed=args.seed % 2147483647))
    import jax
    bias = eng.params["router_bias"]
    eng.params = conditioned(eng.params)
    eng.params["router_bias"] = jax.device_put(
        selection_bias(eng.model_cfg).astype(bias.dtype), bias.sharding)
    a, b = tokens_for(args.seed, sizes, eng.model_cfg.vocab_size)
    t0 = time.monotonic()
    eng.add_request(GenRequest("A", a, max_tokens=sizes["decode"] + sizes["a_extra"],
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
    import jax as _jax
    _jax.effects_barrier()
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
        "dsa": stats.get("dsa"),
        "selected": {str(pos): sets for pos, sets in sorted(picked.items())},
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
        "deepseek_v32_reference",
        os.path.join(HERE, "reference", "deepseek_v32.py"))
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
        n_group=mcfg.n_group, topk_group=mcfg.topk_group,
        index_n_heads=mcfg.index_n_heads,
        index_head_dim=mcfg.index_head_dim, index_topk=mcfg.index_topk,
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

    # the rows whose selected sets are compared: B's decode positions
    set_rows = jnp.asarray(sorted(int(p) for p in rec["selected"]), jnp.int32)

    @functools.partial(jax.jit, static_argnames="select")
    def one_layer(lp, h, select="indexer"):
        with jax.default_matmul_precision("highest"):
            h, sets, _ = ref.layer(rc, lp, h, positions, share,
                                   sizes["q_block"], select)
        return h, sets[set_rows]

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
        bits = {"f32": 8, "bf16_stream": 8, "recency": 8,
                **({"int4": 4} if quantized else {})}
        # between layers a pass's stream waits on the host: four of them
        # at 29k x 7168 float32 would take 3.3 GB of the chip
        hs = {n: np.asarray(plain(params["embed"], bits=b)[jnp.asarray(seq)])
              for n, b in bits.items()}
        ref_sets = []  # per layer [rows, S] bool: the f32 pass's sets
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
                    h = jnp.asarray(hs[n])
                    if n == "bf16_stream":
                        h = h.astype(jnp.bfloat16).astype(jnp.float32)
                    h, sets = one_layer(
                        lp, h, select="recency" if n == "recency"
                        else "indexer")
                    hs[n] = np.asarray(h)
                    if n == "f32":
                        ref_sets.append(np.asarray(sets))
                    del h, sets
                del lp
        out = {n: np.asarray(head(jnp.asarray(hs[n]),
                                  plain(params["final_norm"], bits=b),
                                  plain(params["lm_head"], bits=b)))
               for n, b in bits.items()}
        out["sets"] = ref_sets
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
    late = errors(lps["recency"])
    rounded = errors(full, lps["bf16_stream"])
    agree = float(np.mean(full.argmax(-1) == np.asarray(rec["tokens"])))

    def overlaps():
        """Per layer: the share of the reference's selected rows that the
        program selected too (mean and worst over B's decode positions),
        and the same for a selection by recency against the reference's."""
        rows = sorted(int(p) for p in rec["selected"])
        per_layer = []
        for layer, masks in enumerate(lps["sets"]):
            mine, recent = [], []
            for i, pos in enumerate(rows):
                theirs = set(np.flatnonzero(masks[i]).tolist())
                calls = rec["selected"][str(pos)]
                assert len(calls) == rc.num_hidden_layers, (pos, len(calls))
                mine.append(len(theirs & set(calls[layer])) / len(theirs))
                last = set(range(max(0, pos + 1 - rc.index_topk), pos + 1))
                recent.append(len(theirs & last) / len(theirs))
            per_layer.append({
                "layer": layer, "program_mean": float(np.mean(mine)),
                "program_worst": float(np.min(mine)),
                "recency_mean": float(np.mean(recent))})
        return per_layer

    sets = overlaps()
    out = {
        "config": CONFIG, "variant": args.variant, "seed": args.seed,
        "device": {"platform": jax.devices()[0].platform,
                   "kind": jax.devices()[0].device_kind},
        "context": {"prompt_tokens": n0, "shared_prefix": sizes["prefix"],
                    "decoded": len(rec["tokens"]),
                    "cached_tokens_served": rec["cached_tokens_served"],
                    "mixed_steps": rec["mixed_steps"]},
        "program_vs_reference": got,
        "int4_weights_vs_program": low,
        "recency_selection_vs_program": late,
        "selected_sets_overlap_by_layer": sets,
        "selected_sets_worst_row_overlap": min(
            x["program_worst"] for x in sets),
        "bf16_stream_reference_vs_reference": rounded,
        "conditioning": {
            "scale_fix": SCALE_FIX, "embed": EMBED_RMS, "branch": BRANCH,
            "attn_branch": ATTN_BRANCH,
            "branch_out": BRANCH_OUT, "selection_bias": True},
        "reference_logprob_spread": float(np.std(full)),
        "greedy_token_is_reference_argmax_share": agree,
        "engine": {k: rec[k] for k in (
            "platform", "seconds", "attention_traced", "fallbacks", "moe",
            "attn", "dsa", "kv_pool_shapes", "prefix_cache")},
    }
    out.update(verdict_of(out))
    os.makedirs(OUT_DIR, exist_ok=True)
    tag = f"-{args.variant}" if args.variant else ""
    path = os.path.join(OUT_DIR, f"compare-deepseek-v32{tag}-{args.seed}.json")
    with open(path, "w") as f:
        json.dump(out, f, indent=1)
    print(json.dumps({k: out[k] for k in (
        "program_vs_reference", "int4_weights_vs_program",
        "recency_selection_vs_program", "selected_sets_overlap_by_layer",
        "bf16_stream_reference_vs_reference", "limits",
        "program_within_limits", "int4_refused", "recency_refused",
        "recency_refused_by_logprobs", "context")}), flush=True)
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
    scratch = os.path.join(OUT_DIR, f"compare-deepseek-v32-engine-{args.seed}.json")
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
            print(f"compare_reference_deepseek_v32.py: phase {phase} exited {rc}",
                  file=sys.stderr)
            return rc
    return 0


if __name__ == "__main__":
    sys.exit(main())
