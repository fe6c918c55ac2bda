#!/usr/bin/env python3
"""The sparse selection's pieces, measured alone on the chip before the
cell, at the DeepSeek-V3.2 cell's shapes (one layer of a 9-layer pool; 8,
32 or 64 rows of scores against a 32,768-token page table; 2,048 kept).

PR 38: a chunk over the cell's own 2,063-entry page table (the bucket's
2,048 pages + 15 trailing trash slots), its selection built over the whole
table (PR 35's form) against over the bucket's pages (shipped), with the
real queries' outputs held bit for bit.

PR 35: the form PR 32 shipped (`jax.lax.top_k`, each selected position's
page looked up in the table, a (page, slot) gather: kept here as
`parent_*`) against the shipped one (`ops/attention._dsa_select`: one sort
that carries the physical rows), piece by piece and as the whole decode and
chunk ops; and the sort's two payload layouts (one word where position and
page id fit 30 bits, two where they do not).

    python scripts/dsa_microbench.py   ->  chiprun_out/dsa-microbench.json

DSA_MICROBENCH_SHAPE=kept,keys,pages_a_layer shrinks it for a CPU rehearsal.
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

K, S, LAYER_PAGES = (int(x) for x in os.environ.get(
    "DSA_MICROBENCH_SHAPE", "2048,32768,8192").split(","))
PS, LAYERS = 16, 9


def timed(fn, *args, n=20):
    out = fn(*args)
    jax.block_until_ready(out)
    t0 = time.perf_counter()
    for _ in range(n):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / n * 1e6, out


def parent_select_gather(scores, tables, k_pages):
    """PR 32's form -> (rows [N, K, D], valid)."""
    vals, sel = jax.lax.top_k(scores, K)
    page = jnp.take_along_axis(tables, sel // PS, axis=1)
    return k_pages[page, sel % PS], vals > -jnp.inf


def select_gather(scores, tables, k_pages, off, layer_pages):
    """The shipped form; tables [N, Wp] or one sequence's [Wp]."""
    rows, valid = att._dsa_select(
        scores, *att._dsa_row_words(tables, off, PS, layer_pages), K, "x",
        None)
    return att._gather_rows(k_pages, rows), valid


def parent_decode(q, qi, wi, kp, ip, tables, ctx):
    keys = ip[tables].reshape(tables.shape[0], S, ip.shape[-1])
    sc = att._dsa_scores(qi, wi, keys, "bhd,bsd->bhs")
    sc = jnp.where(jnp.arange(S)[None] < ctx[:, None], sc, -jnp.inf)
    rows, valid = parent_select_gather(sc, tables, kp)
    return att._dsa_attend(q, rows, valid)


def parent_chunk(q, qi, wi, kp, ip, pages, start, block_q):
    c, s = q.shape[0], pages.shape[0] * PS
    keys = ip[pages].reshape(s, ip.shape[-1])
    qpos = start + jnp.arange(c, dtype=jnp.int32)
    tables = jnp.broadcast_to(pages[None, :], (block_q, pages.shape[0]))

    def block(args):
        qb, qib, wb, pos = args
        sc = att._dsa_scores(qib, wb, keys, "qhd,sd->qhs")
        sc = jnp.where(jnp.arange(s)[None] <= pos[:, None], sc, -jnp.inf)
        rows, valid = parent_select_gather(sc, tables, kp)
        return att._dsa_attend(qb, rows, valid)

    def blocks(x):
        return x.reshape((c // block_q, block_q) + x.shape[1:])

    out = jax.lax.map(block, (blocks(q), blocks(qi), blocks(wi),
                              blocks(qpos)))
    return out.reshape(q.shape)


def main():
    dev = jax.devices()[0]
    rec = {"device": {"platform": dev.platform, "kind": dev.device_kind},
           "shapes": {"keys": S, "kept": K, "page_size": PS,
                      "pages_a_layer": LAYER_PAGES, "layers": LAYERS},
           "us": {}, "same_rows_as_parent": {}, "same_as_whole_table": {}}
    us_of = rec["us"]
    rng = np.random.default_rng(0)
    h, d, hi, di = 128, 640, 64, 128
    k1, k2 = jax.random.split(jax.random.PRNGKey(0))
    kp = 0.1 * jax.random.normal(k1, (LAYERS * LAYER_PAGES, PS, d),
                                 jnp.bfloat16)
    ip = jax.random.normal(k2, (LAYERS * LAYER_PAGES, PS, di), jnp.bfloat16)
    off = jnp.int32(3 * LAYER_PAGES)  # layer 3's slice of the flat pool
    pmax = S // PS

    def table(*shape):
        return jnp.asarray(rng.integers(1, LAYER_PAGES, shape), jnp.int32
                           ) + off

    # the selection and the row gather alone, both forms
    for n in (8, 32, 64):
        ctx = rng.integers(S * 7 // 8, S * 15 // 16, n)
        sc = rng.normal(size=(n, S)).astype(np.float32)
        sc[:, 100:140] = 0.5  # equal scores; zeros of both signs
        sc[:, 200:220:2], sc[:, 201:220:2] = 0.0, -0.0
        sc[np.arange(S)[None, :] >= ctx[:, None]] = -np.inf
        sc, tables = jnp.asarray(sc), table(n, pmax)
        us, (want, want_valid) = timed(
            jax.jit(parent_select_gather), sc, tables, kp)
        us_of[f"parent: top_k + lookup + gather[{n},{S}]"] = us
        top = jax.jit(lambda x: jax.lax.top_k(x, K))
        us, (_, sel) = timed(top, sc)
        us_of[f"  top_k[{n},{S}]"] = us
        lookup = jax.jit(
            lambda t, sel: jnp.take_along_axis(t, sel // PS, axis=1))
        us, page = timed(lookup, tables, sel)
        us_of[f"  page lookup (take_along_axis)[{n},{K}]"] = us
        us, _ = timed(jax.jit(lambda k, p, sel: k[p, sel % PS]), kp, page,
                      sel)
        us_of[f"  (page, slot) gather[{n},{K}]"] = us
        us, _ = timed(jax.jit(att._gather_rows), kp, page * PS + sel % PS)
        us_of[f"  flat gather[{n},{K}]"] = us
        for name, lp in (("one word", LAYER_PAGES), ("two words", 1 << 28)):
            f = jax.jit(lambda s, t, k, lp=lp: select_gather(s, t, k, off, lp))
            us, (got, valid) = timed(f, sc, tables, kp)
            us_of[f"shipped: sort ({name}) + flat gather[{n},{S}]"] = us
            rec["same_rows_as_parent"][f"{name}[{n}]"] = bool(
                (got == want).all()) and bool((valid == want_valid).all())
            f = jax.jit(lambda s, t, lp=lp: att._dsa_select(
                s, *att._dsa_row_words(t, off, PS, lp), K, "x", None))
            us, _ = timed(f, sc, tables)
            us_of[f"  words + sort ({name})[{n},{S}]"] = us
        print(json.dumps(us_of), flush=True)

    # the whole ops, one layer, the cell's pools
    kw = dict(page_size=PS, topk=K, page_off=off, layer_pages=LAYER_PAGES)
    for b in (8, 32, 64):
        tables = table(b, pmax)
        ctx = jnp.asarray(rng.integers(S * 7 // 8, S * 15 // 16, b),
                          jnp.int32)
        q = jnp.asarray(rng.normal(size=(b, h, d)), jnp.bfloat16)
        qi = jnp.asarray(rng.normal(size=(b, hi, di)), jnp.bfloat16)
        wi = jnp.asarray(rng.normal(size=(b, hi)), jnp.float32)
        args = (q, qi, wi, kp, ip, tables, ctx)
        us, want = timed(jax.jit(parent_decode), *args)
        us_of[f"parent: decode layer[B={b}]"] = us
        us, got = timed(jax.jit(
            lambda *a: att.dsa_decode_attention(*a, **kw)), *args)
        us_of[f"shipped: dsa_decode_attention[B={b}]"] = us
        rec["same_rows_as_parent"][f"decode output[B={b}]"] = bool(
            (got == want).all())
        print(json.dumps(us_of), flush=True)

    # a 256-token chunk at a 28.7k context, over a table of 2,048 pages and
    # over the cell's own (engine/kv_cache.page_table_width: 15 trailing
    # trash slots, so 33,008 positions, which the sort works at the next
    # power of two). PR 38: the shipped op is handed the bucket's pages as
    # the selection's extent, as models/llama hands them; `whole table` is
    # PR 35's form, the same op over every entry
    c, start = 256, jnp.int32(S * 7 // 8)
    tail = att.chunk_table_tail(c, PS)
    fits = min(c, S - int(start))  # queries inside the bucket: all, at 32k
    q = jnp.asarray(rng.normal(size=(c, h, d)), jnp.bfloat16)
    qi = jnp.asarray(rng.normal(size=(c, hi, di)), jnp.bfloat16)
    wi = jnp.asarray(rng.normal(size=(c, hi)), jnp.float32)

    def chunk_op(bq, key_pages):
        return jax.jit(lambda *a: att.dsa_chunk_attention(
            *a, block_q=bq, key_pages=key_pages, **kw))

    for wp, bq in ((pmax, 32), (pmax + tail, 32), (pmax, 64)):
        cpages = jnp.concatenate([table(pmax), jnp.zeros(
            (wp - pmax,), jnp.int32) + off])
        args = (q, qi, wi, kp, ip, cpages, start)
        label = f"[C=256,start={int(start)},pages={wp},block_q={bq}]"
        us, want = timed(jax.jit(
            lambda *a, bq=bq: parent_chunk(*a, block_q=bq)), *args, n=5)
        us_of[f"parent: chunk layer{label}"] = us
        shipped, whole_table = chunk_op(bq, pmax), chunk_op(bq, None)
        us, got = timed(shipped, *args, n=5)
        us_of[f"shipped: dsa_chunk_attention{label}"] = us
        rec["same_rows_as_parent"][f"chunk output{label}"] = bool(
            (got[:fits] == want[:fits]).all())
        if wp == pmax:
            continue
        us, whole = timed(whole_table, *args, n=5)
        us_of[f"whole table (PR 35): dsa_chunk_attention{label}"] = us
        rec["same_as_whole_table"][f"chunk output{label}"] = bool(
            (got[:fits] == whole[:fits]).all())
        # a prompt's last chunk: its padded window crosses the bucket's
        # end, and only its real queries (64 here) owe the parent's bits
        real = 4 * PS
        last = (q, qi, wi, kp, ip, cpages, jnp.int32(S - real))
        got, whole = shipped(*last), whole_table(*last)
        rec["same_as_whole_table"][
            f"last chunk's {real} real queries[start={S - real}]"] = bool(
                (got[:real] == whole[:real]).all())
        rec["same_as_whole_table"][
            "  (its padded queries, not owed)"] = bool(
                (got[real:] == whole[real:]).all())
    os.makedirs("chiprun_out", exist_ok=True)
    with open("chiprun_out/dsa-microbench.json", "w") as f:
        json.dump(rec, f, indent=1)
    print(json.dumps(rec, indent=1))


if __name__ == "__main__":
    main()
