#!/usr/bin/env python3
"""The mixed step's ragged attention kernel measured alone on the chip, before
the cells: one layer-call of `ops/ragged_attention.ragged_paged_attention` at
a cell's mixed-step shape (64 decode slots of which some are live, one
256-token chunk, tables as wide as the cell's), this tree's kernel beside
any other copy of the module handed by path (the parent's, from `git show`).

    python scripts/ragged_microbench.py [--impl name=path/to/ragged_attention.py ...]
        ->  chiprun_out/ragged-microbench.json

Cases (RAGGED_MICROBENCH_CASES picks by name, comma-separated):
  falcon.liveN  20 / 4 heads of 128, bf16, tables 399 wide, N of 64 slots live
                at 1,900 tokens, a chunk at start 512 (N = 0, 13, 32, 64)
  kimi.live2    64 heads on ONE shared 640-lane latent row (V read from K),
                2 of 64 slots live at 4,500 tokens, a chunk at start 4,096

Every implementation is timed `pinned`: handed what the parent's mixed step
hands, an empty slot at context 1 on the trash page. This tree's is also timed
`empty0`, handed context 0 there (what `ragged_mixed_attention`'s
`kernel_lens` hands since PR 45; the parent's kernel cannot take it: it
clamps the horizon to 1, reads the trash page all the same and masks the
whole row, which is NaN). A timing is one program of 10 calls in a `lax.scan`
(ten layers; each call's queries hang on the last result, so nothing is
hoisted), in 5 groups of 4 calls of which the median is kept. Every first
result is held against the first implementation's `pinned` one: live rows
and the chunk's rows bit for bit, the empty rows the pinned ones or, under
`empty0`, zero.

RAGGED_MICROBENCH_SHRINK=16 divides contexts and tables for a CPU rehearsal
(interpret mode, one timing)."""
import argparse
import importlib.util
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np

from dynamo_tpu.ops import ragged_attention

PAGE, SLOTS, CHUNK, CALLS_A_PROGRAM, CALLS, GROUPS = 16, 64, 256, 10, 4, 5
SHRINK = int(os.environ.get("RAGGED_MICROBENCH_SHRINK", "1"))

# name -> (heads, kv heads, head dim, shared row, live slots, context,
#          chunk start, table width)
CASES = {
    **{f"falcon.live{n}": (20, 4, 128, False, n, 1900, 512, 399)
       for n in (0, 13, 32, 64)},
    "kimi.live2": (64, 1, 640, True, 2, 4500, 4096, 399),
}


def load(path: str):
    spec = importlib.util.spec_from_file_location(
        "ragged_attention_" + str(abs(hash(path))), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def operands(rng, heads, n_kv, d, shared, live, ctx, start, width):
    """A cell's mixed step as the engine lays it out: live slots spread over
    the batch, each with its own pages; empty slots all trash page 0."""
    ctx, start = max(ctx // SHRINK, 1), start // SHRINK // PAGE * PAGE
    pages_a_row = -(-ctx // PAGE)
    chunk_pages = (start + CHUNK) // PAGE
    width = max(width // SHRINK, pages_a_row, chunk_pages + 1)
    n_pool = 1 + live * pages_a_row + chunk_pages
    lanes = n_kv * d
    k = jnp.asarray(rng.normal(size=(n_pool, PAGE, lanes)), jnp.bfloat16)
    v = (jnp.zeros((n_pool, PAGE, 0), jnp.bfloat16) if shared else
         jnp.asarray(rng.normal(size=(n_pool, PAGE, lanes)), jnp.bfloat16))
    tables = np.zeros((SLOTS + 1, width), np.int32)
    mask = np.zeros((SLOTS,), bool)
    mask[np.sort(rng.permutation(SLOTS)[:live])] = True
    nxt = 1
    for slot in np.flatnonzero(mask):
        tables[slot, :pages_a_row] = np.arange(nxt, nxt + pages_a_row)
        nxt += pages_a_row
    tables[SLOTS, :chunk_pages] = np.arange(nxt, nxt + chunk_pages)
    lens = {"pinned": np.where(mask, ctx, 1), "empty0": np.where(mask, ctx, 0)}
    q = jnp.asarray(rng.normal(size=(SLOTS + CHUNK, heads, d)), jnp.bfloat16)
    out = {}
    for name, cl in lens.items():
        kv_lens = np.concatenate([cl, [start + CHUNK]]).astype(np.int32)
        q_starts = np.concatenate(
            [np.maximum(cl - 1, 0), [start]]).astype(np.int32)
        out[name] = (jnp.asarray(kv_lens), jnp.asarray(q_starts))
    return q, k, v, jnp.asarray(tables), out, mask


def program(mod, n_kv, on_chip):
    def one(q, k, v, tables, kv_lens, q_starts):
        return mod.ragged_paged_attention(
            q, k, v, tables, kv_lens, q_starts, page_size=PAGE,
            num_kv_heads=n_kv, num_decode=SLOTS, interpret=not on_chip)

    def ten(q, k, v, tables, kv_lens, q_starts):
        def body(o, _):
            o = one(q + (1e-3 * o).astype(q.dtype), k, v, tables, kv_lens,
                    q_starts)
            return o, None
        return jax.lax.scan(body, jnp.zeros_like(q), None,
                            length=CALLS_A_PROGRAM)[0]
    return jax.jit(one), jax.jit(ten)


def us_a_call(fn, args, on_chip) -> float:
    jax.block_until_ready(fn(*args))
    groups = []
    for _ in range(GROUPS if on_chip else 1):
        t0 = time.perf_counter()
        for _ in range(CALLS if on_chip else 1):
            out = fn(*args)
        jax.block_until_ready(out)
        groups.append((time.perf_counter() - t0)
                      / ((CALLS if on_chip else 1) * CALLS_A_PROGRAM) * 1e6)
    return sorted(groups)[len(groups) // 2]


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--impl", action="append", default=[],
                    help="name=path of another copy of ragged_attention.py; "
                         "the first is the one results are held against")
    ap.add_argument("--out", default="ragged-microbench.json")
    args = ap.parse_args()
    impls = {}
    for spec in args.impl:
        name, path = spec.split("=", 1)
        impls[name] = load(path)
    impls["change"] = ragged_attention
    dev = jax.devices()[0]
    on_chip = dev.platform == "tpu"
    picked = os.environ.get("RAGGED_MICROBENCH_CASES")
    cases = {k: v for k, v in CASES.items()
             if not picked or k in picked.split(",")}
    rec = {"device": {"platform": dev.platform, "kind": dev.device_kind},
           "slots": SLOTS, "chunk": CHUNK, "page": PAGE, "shrink": SHRINK,
           "calls_a_program": CALLS_A_PROGRAM, "us_a_call": {}, "equal": {}}
    bad = []
    for case, (heads, n_kv, d, shared, live, ctx, start, width) in \
            cases.items():
        rng = np.random.default_rng(45)
        q, k, v, tables, lens, mask = operands(
            rng, heads, n_kv, d, shared, live, ctx, start, width)
        want = None
        for name, mod in impls.items():
            one, ten = program(mod, n_kv, on_chip)
            for handed, (kv_lens, q_starts) in lens.items():
                if handed == "empty0" and mod is not ragged_attention:
                    continue
                got = np.asarray(one(q, k, v, tables, kv_lens, q_starts)
                                 .astype(jnp.float32))
                if want is None:
                    want = got
                rows = np.concatenate([mask, np.ones((CHUNK,), bool)])
                dead = got[:SLOTS][~mask]
                same = {
                    "live_and_chunk_rows_bit_for_bit": bool(
                        (got[rows] == want[rows]).all()),
                    "empty_rows": "zero" if not dead.any() else (
                        "pinned" if (dead == want[:SLOTS][~mask]).all()
                        else "OTHER"),
                    "finite": bool(np.isfinite(got).all())}
                key = f"{case}.{name}.{handed}"
                rec["equal"][key] = same
                if not (same["live_and_chunk_rows_bit_for_bit"]
                        and same["finite"] and same["empty_rows"] != "OTHER"):
                    bad.append(key)
                rec["us_a_call"][key] = round(us_a_call(
                    ten, (q, k, v, tables, kv_lens, q_starts), on_chip), 2)
                print(key, rec["us_a_call"][key], same, flush=True)
    os.makedirs("chiprun_out", exist_ok=True)
    with open(os.path.join("chiprun_out", args.out), "w") as f:
        json.dump(rec, f, indent=1)
    if bad:
        print("NOT the first implementation's rows:", bad)
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
