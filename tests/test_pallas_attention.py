"""Pallas kernels vs XLA reference ops (interpret mode on CPU).

Covers: paged decode attention (GQA, ragged context lens, inactive slots) and
prefill flash attention (causal + padded tail), plus the shard_map TP path on
the 8-device CPU mesh.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dynamo_tpu.ops import attention as att
from dynamo_tpu.ops import pallas_attention as pa


def _decode_inputs(key, bsz=4, n_heads=8, n_kv=2, head_dim=128, page_size=16,
                   num_pages=64, pmax=8):
    ks = jax.random.split(key, 5)
    q = jax.random.normal(ks[0], (bsz, n_heads, head_dim), jnp.float32)
    k_pages = jax.random.normal(
        ks[1], (num_pages, page_size, n_kv * head_dim), jnp.float32)
    v_pages = jax.random.normal(
        ks[2], (num_pages, page_size, n_kv * head_dim), jnp.float32)
    # distinct non-zero pages per sequence
    bt = (
        jnp.arange(bsz * pmax, dtype=jnp.int32).reshape(bsz, pmax) % (num_pages - 1)
    ) + 1
    # ragged: 1 token .. several pages; one inactive slot (ctx 0)
    cl = jnp.array([1, page_size * 3 + 5, page_size * pmax, 0][:bsz], jnp.int32)
    return q, k_pages, v_pages, bt, cl


def test_decode_matches_xla():
    q, kp, vp, bt, cl = _decode_inputs(jax.random.PRNGKey(0))
    ref = att.paged_attention_decode_xla(q, kp, vp, bt, cl, page_size=16)
    out = pa.paged_attention_decode(q, kp, vp, bt, cl, page_size=16,
                                    num_kv_heads=2, interpret=True)
    # slot 3 is inactive (ctx 0): pallas emits zeros, XLA emits uniform junk —
    # compare active slots only.
    np.testing.assert_allclose(np.asarray(out[:3]), np.asarray(ref[:3]),
                               rtol=2e-5, atol=2e-5)
    assert not np.isnan(np.asarray(out)).any()


def test_decode_single_kv_head_mha():
    q, kp, vp, bt, cl = _decode_inputs(jax.random.PRNGKey(1), n_heads=4, n_kv=4)
    ref = att.paged_attention_decode_xla(q, kp, vp, bt, cl, page_size=16)
    out = pa.paged_attention_decode(q, kp, vp, bt, cl, page_size=16,
                                    num_kv_heads=4, interpret=True)
    np.testing.assert_allclose(np.asarray(out[:3]), np.asarray(ref[:3]),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("s,seq_len", [(128, 128), (256, 200), (48, 33), (16, 5)])
def test_prefill_matches_xla(s, seq_len):
    ks = jax.random.split(jax.random.PRNGKey(2), 3)
    n_heads, n_kv, head_dim = 8, 2, 64
    q = jax.random.normal(ks[0], (s, n_heads, head_dim), jnp.float32)
    k = jax.random.normal(ks[1], (s, n_kv, head_dim), jnp.float32)
    v = jax.random.normal(ks[2], (s, n_kv, head_dim), jnp.float32)
    ref = att.prefill_attention_xla(q, k, v, seq_len)
    out = pa.prefill_attention(q, k, v, seq_len, interpret=True)
    # only rows < seq_len are meaningful (padded rows are garbage both ways)
    np.testing.assert_allclose(np.asarray(out[:seq_len]),
                               np.asarray(ref[:seq_len]), rtol=2e-5, atol=2e-5)


def test_dispatch_backend_selection():
    q, kp, vp, bt, cl = _decode_inputs(jax.random.PRNGKey(3))
    with att.attention_context("pallas_interpret", None):
        out = att.paged_attention_decode(q, kp, vp, bt, cl, page_size=16)
    with att.attention_context("xla", None):
        ref = att.paged_attention_decode(q, kp, vp, bt, cl, page_size=16)
    np.testing.assert_allclose(np.asarray(out[:3]), np.asarray(ref[:3]),
                               rtol=2e-5, atol=2e-5)


def test_decode_shard_map_tp():
    """Pallas decode under shard_map on the 8-device CPU mesh (tp=4, dp=2)."""
    from dynamo_tpu.parallel.mesh import MeshConfig, build_mesh

    mesh = build_mesh(MeshConfig(data_parallel=2, tensor_parallel=4))
    q, kp, vp, bt, cl = _decode_inputs(
        jax.random.PRNGKey(4), bsz=4, n_heads=8, n_kv=4
    )
    ref = att.paged_attention_decode_xla(q, kp, vp, bt, cl, page_size=16)
    with att.attention_context("pallas_interpret", mesh):
        out = att.paged_attention_decode(q, kp, vp, bt, cl, page_size=16)
    np.testing.assert_allclose(np.asarray(out[:3]), np.asarray(ref[:3]),
                               rtol=2e-5, atol=2e-5)


def test_engine_generates_with_pallas_backend():
    """End-to-end: engine produces identical greedy tokens on pallas vs xla."""
    from dynamo_tpu.engine.config import EngineConfig
    from dynamo_tpu.engine.engine import Engine
    from dynamo_tpu.engine.request import GenRequest

    def run(backend):
        eng = Engine(EngineConfig(
            model="tiny-debug", page_size=16, num_pages=64, max_num_seqs=2,
            max_seq_len=128, attention_backend=backend,
        ))
        return eng.generate(GenRequest(
            "r1", [1, 2, 3, 4, 5], max_tokens=8, temperature=0.0,
            ignore_eos=True,
        ))
    toks_pallas = run("pallas_interpret")
    toks_xla = run("xla")
    assert toks_pallas == toks_xla


def test_prefill_shard_map_tp():
    from dynamo_tpu.parallel.mesh import MeshConfig, build_mesh

    mesh = build_mesh(MeshConfig(tensor_parallel=4))
    ks = jax.random.split(jax.random.PRNGKey(5), 3)
    s, n_heads, n_kv, head_dim = 64, 8, 4, 32
    q = jax.random.normal(ks[0], (s, n_heads, head_dim), jnp.float32)
    k = jax.random.normal(ks[1], (s, n_kv, head_dim), jnp.float32)
    v = jax.random.normal(ks[2], (s, n_kv, head_dim), jnp.float32)
    ref = att.prefill_attention_xla(q, k, v, 50)
    with att.attention_context("pallas_interpret", mesh):
        out = att.prefill_attention(q, k, v, 50)
    np.testing.assert_allclose(np.asarray(out[:50]), np.asarray(ref[:50]),
                               rtol=2e-5, atol=2e-5)


def test_chunk_prefill_kernel_matches_xla():
    """Pallas chunked-prefill flash vs the XLA gather path: prefix in pages,
    chunk tokens freshly written, causal over absolute positions."""
    import numpy as np

    rng = np.random.default_rng(11)
    ps, n_kv, d, h = 16, 2, 128, 8
    kvd = n_kv * d
    npages, width = 64, 12
    kp = jnp.asarray(rng.normal(size=(npages, ps, kvd)), jnp.float32)
    vp = jnp.asarray(rng.normal(size=(npages, ps, kvd)), jnp.float32)
    pages = jnp.asarray(list(range(1, 9)) + [0, 0, 0, 0], jnp.int32)
    for start, c in ((48, 16), (0, 32), (32, 8)):
        q = jnp.asarray(rng.normal(size=(c, h, d)), jnp.float32)
        ref = att.chunk_attention(q, kp, vp, pages, start, page_size=ps)
        from dynamo_tpu.ops.pallas_attention import chunk_prefill_attention

        out = chunk_prefill_attention(
            q, kp, vp, pages, start, page_size=ps, num_kv_heads=n_kv,
            interpret=True)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=2e-5, atol=2e-5)


def test_chunk_attention_scoped_dispatch():
    import numpy as np

    rng = np.random.default_rng(12)
    ps, n_kv, d, h = 16, 2, 64, 4
    kp = jnp.asarray(rng.normal(size=(16, ps, n_kv * d)), jnp.float32)
    vp = jnp.asarray(rng.normal(size=(16, ps, n_kv * d)), jnp.float32)
    pages = jnp.asarray([1, 2, 3, 4], jnp.int32)
    q = jnp.asarray(rng.normal(size=(16, h, d)), jnp.float32)
    ref = att.chunk_attention(q, kp, vp, pages, 16, page_size=ps)
    with att.attention_context("pallas_interpret", None):
        out = att.chunk_attention(q, kp, vp, pages, 16, page_size=ps)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


# ---- the decode kernel works over live KV only (PR 28): one grid step a
# slot, a loop over the slot's own superblocks, nothing for an empty slot --

LIVE_PS, LIVE_PMAX, LIVE_POOL = 16, 24, 128  # a table of 384 tokens = 3 blocks
LIVE_WHOLE = LIVE_PS * LIVE_PMAX

# name -> context per slot; 0 is an empty slot (context 0, an all-trash
# table). Superblocks are 128 tokens and the ring holds 4 of them.
LIVE_BATCHES = {
    "empty_first": [0, 0, 129, LIVE_WHOLE],
    "empty_last": [127, 128, 0, 0],
    "empty_runs_between": [1, 0, 0, LIVE_WHOLE, 0, 129, 0, 0, 128],
    "all_empty_but_one": [0, 0, 0, 0, 0, 200, 0, 0],
    "all_empty": [0, 0, 0, 0],
    "ctx_1_127_128_129_whole": [1, 127, 128, 129, LIVE_WHOLE],
    "more_blocks_than_ring": [LIVE_WHOLE, LIVE_WHOLE, LIVE_WHOLE, 300],
    "fewer_blocks_than_ring": [0, 5, 0],
}

# name -> (query heads, KV heads, head dim, int8 rows, V read from K,
# tolerance against the XLA twin). The shared row meets bf16 queries and
# probabilities in the kernel (_kv_block), float32 ones in the twin.
LIVE_POOLS = {
    "per_head_bf16": (8, 2, 128, False, False, 2e-5),
    "int8kv": (8, 2, 128, True, False, 2e-5),
    "shared_row": (4, 1, 256, False, True, 3e-2),
}


def _live_case(pool, ctx, seed=0):
    """(q, k_pages, v_pages, tables, context_lens, kwargs): live slots own
    disjoint pages up to their context; table tails and empty slots' tables
    are trash page 0, as the engine leaves them."""
    h, n_kv, d, quantized, shared, _ = LIVE_POOLS[pool]
    rng = np.random.default_rng(seed)
    n = LIVE_POOL * LIVE_PS
    q = jnp.asarray(rng.normal(size=(len(ctx), h, d)), jnp.float32)
    kf = jnp.asarray(rng.normal(size=(n, n_kv, d)), jnp.float32)
    vf = jnp.asarray(rng.normal(size=(n, n_kv, d)), jnp.float32)
    if quantized:
        w = att.kv_lane_width(n_kv, d, True)
        kp = att.pack_kv_rows(kf, w).reshape(LIVE_POOL, LIVE_PS, w)
        vp = att.pack_kv_rows(vf, w).reshape(LIVE_POOL, LIVE_PS, w)
    else:
        kp = kf.reshape(LIVE_POOL, LIVE_PS, n_kv * d).astype(jnp.bfloat16)
        vp = (jnp.zeros((LIVE_POOL, LIVE_PS, 0), jnp.bfloat16) if shared
              else vf.reshape(kp.shape).astype(jnp.bfloat16))
    tables = np.zeros((len(ctx), LIVE_PMAX), np.int32)
    nxt = 1
    for slot, c in enumerate(ctx):
        pages = -(-c // LIVE_PS)
        tables[slot, :pages] = np.arange(nxt, nxt + pages)
        nxt += pages
    assert nxt <= LIVE_POOL
    return (q, kp, vp, jnp.asarray(tables), jnp.asarray(ctx, jnp.int32),
            dict(page_size=LIVE_PS, num_kv_heads=n_kv))


@pytest.mark.parametrize("batch", list(LIVE_BATCHES))
@pytest.mark.parametrize("pool", list(LIVE_POOLS))
def test_decode_live_kv_matches_xla_on_live_rows(pool, batch):
    ctx = LIVE_BATCHES[batch]
    q, kp, vp, bt, cl, kw = _live_case(pool, ctx)
    out = np.asarray(pa.paged_attention_decode(q, kp, vp, bt, cl,
                                               interpret=True, **kw))
    ref = np.asarray(att.paged_attention_decode_xla(
        q, kp, vp, bt, cl, lane_blocks=1, **kw))
    live = np.asarray(ctx) > 0
    tol = LIVE_POOLS[pool][-1]
    assert np.isfinite(out).all()
    np.testing.assert_allclose(out[live], ref[live], rtol=tol, atol=tol)
    # an empty slot owns no block: zeros, whatever its neighbours hold
    assert not out[~live].any()


def _poison(pages, ids, n_kv, d):
    """`pages` with every row of the pages `ids` reading NaN."""
    if pages.dtype != jnp.int8:
        return pages.at[ids].set(jnp.nan)
    # packed rows: a NaN scale (bf16 0x7FC0, low byte first) on every head
    kvd = n_kv * d
    bad = np.ones(pages.shape[1:], np.int8)
    bad[:, kvd:kvd + 2 * n_kv:2] = -64  # 0xC0
    bad[:, kvd + 1:kvd + 2 * n_kv:2] = 0x7F
    return pages.at[ids].set(jnp.asarray(bad))


@pytest.mark.parametrize("pool", list(LIVE_POOLS))
def test_decode_reads_live_kv_only(pool):
    """The property itself: whatever no live sequence owns below its
    context (trash page 0, the pages table tails name, free pages) reads
    NaN, and live rows come out bit for bit as from the clean pool."""
    ctx = LIVE_BATCHES["empty_runs_between"]
    q, kp, vp, bt, cl, kw = _live_case(pool, ctx, seed=3)
    _, n_kv, d, _, shared, _ = LIVE_POOLS[pool]
    clean = np.asarray(pa.paged_attention_decode(q, kp, vp, bt, cl,
                                                 interpret=True, **kw))
    owned = {int(p) for row, c in zip(np.asarray(bt), ctx)
             for p in row[:-(-c // LIVE_PS)]}
    free = sorted(set(range(LIVE_POOL)) - owned)
    assert 0 in free and len(free) > 1
    # table tails name stale free pages too, not the trash page alone
    tables = np.asarray(bt).copy()
    for slot, c in enumerate(ctx):
        tables[slot, -(-c // LIVE_PS) + 1:] = free[-1]
    kp = _poison(kp, jnp.asarray(free), n_kv, d)
    if not shared:
        vp = _poison(vp, jnp.asarray(free), n_kv, d)
    out = np.asarray(pa.paged_attention_decode(
        q, kp, vp, jnp.asarray(tables), cl, interpret=True, **kw))
    live = np.asarray(ctx) > 0
    assert np.isfinite(out).all()
    np.testing.assert_array_equal(out[live], clean[live])
    assert not out[~live].any()


def test_decode_step_hands_the_kernel_context_0_for_an_empty_slot(monkeypatch):
    """`decode_step` reads liveness off the tables (an empty slot's is all
    trash page 0) and the dispatcher hands the Pallas kernel context 0
    there; the XLA twin keeps the engine's pin at context 1."""
    from dynamo_tpu.models import llama
    from dynamo_tpu.models.config import ModelConfig

    # 2 KV heads of 64: a 128-lane row, which the kernels' lane gate takes
    cfg = dataclasses.replace(ModelConfig.from_model_name("tiny-debug"),
                              head_dim=64)
    params = llama.init_params(cfg, jax.random.PRNGKey(0))
    ps, pages, b = 16, 8, 3
    lanes = cfg.cache_kv_heads * cfg.head_dim
    kp = jnp.zeros((cfg.num_layers, pages, ps, lanes), jnp.bfloat16)
    tables = jnp.asarray([[0, 0], [3, 0], [0, 0]], jnp.int32)
    ctx = jnp.asarray([1, 6, 1], jnp.int32)  # the engine's pin for 0 and 2
    seen = {}
    real = pa.paged_attention_decode

    def spy(q, k, v, bt, cl, **kw):
        seen["pallas"] = cl
        return real(q, k, v, bt, cl, **kw)

    real_xla = att.paged_attention_decode_xla

    def spy_xla(q, k, v, bt, cl, **kw):
        seen["xla"] = cl
        return real_xla(q, k, v, bt, cl, **kw)

    monkeypatch.setattr(pa, "paged_attention_decode", spy)
    monkeypatch.setattr(att, "paged_attention_decode_xla", spy_xla)

    def step(backend):
        with att.attention_context(backend, None), jax.disable_jit():
            return llama.decode_step(
                cfg, params, jnp.zeros((b,), jnp.int32),
                jnp.asarray([0, 5, 0], jnp.int32), tables, ctx, kp, kp,
                page_size=ps)

    got = step("pallas_interpret")
    want = step("xla")
    assert np.asarray(seen["pallas"]).tolist() == [0, 6, 0]
    assert np.asarray(seen["xla"]).tolist() == [1, 6, 1]
    np.testing.assert_allclose(
        np.asarray(got.logits[1], np.float32),
        np.asarray(want.logits[1], np.float32), rtol=2e-2, atol=2e-2)
    assert np.isfinite(np.asarray(got.logits, np.float32)).all()
