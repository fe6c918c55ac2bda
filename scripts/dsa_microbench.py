#!/usr/bin/env python3
"""Step 1 of PR 32, measured alone on the chip before the cell: what the
sparse selection's pieces cost at the DeepSeek-V3.2 cell's shapes (one
layer; 64 decode slots or a block of 32 chunk queries against a 32,768-token
page table; 2,048 of them kept), and what the other forms would cost.

    python scripts/dsa_microbench.py   ->  chiprun_out/dsa-microbench.json
"""
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np

from dynamo_tpu.ops import attention as att

K, S, PS = 2048, 32768, 16


def timed(fn, *args, n=10):
    out = fn(*args)
    jax.block_until_ready(out)
    t0 = time.perf_counter()
    for _ in range(n):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / n * 1e6, out


def select_bisect(scores, k):
    """Exact top-k as a SET without a sort: the k-th largest key by 32 steps
    of bisection on the floats' ordered bit patterns, ties to the lower
    index, then the chosen positions compacted by a prefix count."""
    n, s = scores.shape
    u = jax.lax.bitcast_convert_type(scores, jnp.uint32)
    key = jnp.where(u >> 31 == 1, ~u, u | jnp.uint32(0x80000000))

    def body(i, t):
        cand = t | (jnp.uint32(1) << (31 - i).astype(jnp.uint32))
        cnt = jnp.sum(key >= cand[:, None], axis=1)
        return jnp.where(cnt >= k, cand, t)

    t = jax.lax.fori_loop(0, 32, body, jnp.zeros((n,), jnp.uint32))
    gt, eq = key > t[:, None], key == t[:, None]
    need = k - jnp.sum(gt, axis=1)
    take = gt | (eq & (jnp.cumsum(eq, axis=1) <= need[:, None]))
    c = jnp.cumsum(take.astype(jnp.int32), axis=1)
    j = jnp.arange(1, k + 1, dtype=jnp.int32)
    sel = jax.vmap(lambda row: jnp.searchsorted(row, j, side="left"))(c)
    sel = jnp.minimum(sel, s - 1).astype(jnp.int32)
    valid = jnp.take_along_axis(scores, sel, axis=1) > -jnp.inf
    return sel, valid


def main():
    dev = jax.devices()[0]
    rec = {"device": {"platform": dev.platform, "kind": dev.device_kind},
           "shapes": {"keys": S, "kept": K, "page_size": PS}, "us": {}}
    rng = np.random.default_rng(0)
    for n in (8, 32, 64):
        ctx = rng.integers(28000, 31000, n)
        sc = rng.normal(size=(n, S)).astype(np.float32)
        sc[np.arange(S)[None, :] >= ctx[:, None]] = -np.inf
        sc = jnp.asarray(sc)
        top = jax.jit(lambda x: jax.lax.top_k(x, K))
        us, (vals, ref_sel) = timed(top, sc)
        rec["us"][f"top_k[{n},{S}]"] = us
        us, (sel, valid) = timed(jax.jit(lambda x: select_bisect(x, K)), sc)
        rec["us"][f"bisect_select[{n},{S}]"] = us
        same = all(set(np.asarray(a).tolist()) == set(np.asarray(b).tolist())
                   for a, b in zip(ref_sel, sel))
        rec[f"bisect_equals_top_k[{n}]"] = bool(same and bool(valid.all()))
        us, _ = timed(jax.jit(lambda x: jax.lax.approx_max_k(x, K)), sc)
        rec["us"][f"approx_max_k[{n},{S}] (not exact: never served)"] = us
        us, _ = timed(jax.jit(lambda x: jnp.argsort(-x, axis=1)[:, :K]), sc)
        rec["us"][f"argsort[{n},{S}]"] = us
        print(json.dumps(rec["us"]), flush=True)

    # the whole ops, one layer, the cell's pools
    pages, h, d, hi, di = 8192, 128, 640, 64, 128
    kp = jnp.asarray(rng.normal(size=(pages, PS, d)) * 0.1, jnp.bfloat16)
    ip = jnp.asarray(rng.normal(size=(pages, PS, di)), jnp.bfloat16)
    pmax = S // PS
    for b in (8, 64):
        tables = jnp.asarray(rng.integers(1, pages, (b, pmax)), jnp.int32)
        ctx = jnp.asarray(rng.integers(28000, 31000, b), jnp.int32)
        q = jnp.asarray(rng.normal(size=(b, h, d)), jnp.bfloat16)
        qi = jnp.asarray(rng.normal(size=(b, hi, di)), jnp.bfloat16)
        wi = jnp.asarray(rng.normal(size=(b, hi)), jnp.float32)
        f = jax.jit(lambda *a: att.dsa_decode_attention(
            *a, page_size=PS, topk=K))
        us, _ = timed(f, q, qi, wi, kp, ip, tables, ctx)
        rec["us"][f"dsa_decode_attention[B={b}]"] = us
        # its parts
        def scores_only(qi, wi, ip, tables, ctx):
            keys = ip[tables].reshape(b, S, di)
            sc = att._dsa_scores(qi, wi, keys, "bhd,bsd->bhs")
            return jnp.where(jnp.arange(S)[None] < ctx[:, None], sc, -jnp.inf)
        us, sc = timed(jax.jit(scores_only), qi, wi, ip, tables, ctx)
        rec["us"][f"  indexer scores[B={b}]"] = us
        us, (_, sel) = timed(jax.jit(lambda x: jax.lax.top_k(x, K)), sc)
        rec["us"][f"  top_k[B={b}]"] = us
        def attend(q, kp, tables, sel):
            rows = att._gather_rows(kp, tables, sel, PS)
            return att._dsa_attend(q, rows, jnp.ones(sel.shape, bool))
        us, _ = timed(jax.jit(attend), q, kp, tables, sel)
        rec["us"][f"  gather + attend[B={b}]"] = us
        # dense MLA decode over the whole context (what the selection saves)
        from dynamo_tpu.ops import pallas_attention as pa
        g = jax.jit(lambda q, kp, t, c: pa.paged_attention_decode(
            q, kp, None, t, c, page_size=PS, num_kv_heads=1))
        try:
            us, _ = timed(g, q, kp, tables, ctx)
            rec["us"][f"dense decode kernel, 128 heads[B={b}]"] = us
        except Exception as e:  # noqa: BLE001
            rec["us"][f"dense decode kernel, 128 heads[B={b}]"] = repr(e)[:300]
        print(json.dumps(rec["us"]), flush=True)

    # a 256-token chunk at a 29k context: gathered form (shipped) ...
    c, b = 256, 64
    wp = pmax + c // PS - 1
    cpages = jnp.asarray(rng.integers(1, pages, (wp,)), jnp.int32)
    q = jnp.asarray(rng.normal(size=(c, h, d)), jnp.bfloat16)
    qi = jnp.asarray(rng.normal(size=(c, hi, di)), jnp.bfloat16)
    wi = jnp.asarray(rng.normal(size=(c, hi)), jnp.float32)
    for bq in (16, 32, 64):
        f = jax.jit(lambda *a, bq=bq: att.dsa_chunk_attention(
            *a, page_size=PS, topk=K, block_q=bq))
        try:
            us, _ = timed(f, q, qi, wi, kp, ip, cpages, jnp.int32(28672), n=3)
            rec["us"][f"dsa_chunk_attention[C=256,start=28672,block_q={bq}]"] = us
        except Exception as e:  # noqa: BLE001
            rec["us"][f"dsa_chunk_attention[block_q={bq}]"] = repr(e)[:300]
    # ... and the mask form's floor: the ragged kernel, dense, same shapes
    from dynamo_tpu.ops import ragged_attention as ra
    tables = jnp.asarray(rng.integers(1, pages, (b, pmax)), jnp.int32)
    ctx = jnp.asarray(rng.integers(28000, 31000, b), jnp.int32)
    for live in (0, 8):
        tabs = jnp.zeros((b + 1, wp), jnp.int32)
        tabs = tabs.at[:live, :pmax].set(tables[:live]).at[b].set(cpages)
        kl = jnp.concatenate([jnp.where(jnp.arange(b) < live, ctx, 1),
                              jnp.asarray([28672 + c], jnp.int32)])
        qs = jnp.concatenate([jnp.maximum(kl[:b] - 1, 0),
                              jnp.asarray([28672], jnp.int32)])
        qq = jnp.asarray(rng.normal(size=(b + c, h, d)), jnp.bfloat16)
        g = jax.jit(lambda qq, kp, tabs, kl, qs: ra.ragged_paged_attention(
            qq, kp, None, tabs, kl, qs, page_size=PS, num_kv_heads=1,
            num_decode=b))
        try:
            us, _ = timed(g, qq, kp, tabs, kl, qs, n=3)
            rec["us"][f"dense ragged kernel, 128 heads, chunk 256 @ 28672, "
                      f"{live} live decode rows"] = us
        except Exception as e:  # noqa: BLE001
            rec["us"][f"dense ragged kernel[{live} live]"] = repr(e)[:300]
    os.makedirs("chiprun_out", exist_ok=True)
    with open("chiprun_out/dsa-microbench.json", "w") as f:
        json.dump(rec, f, indent=1)
    print(json.dumps(rec, indent=1))


if __name__ == "__main__":
    main()
