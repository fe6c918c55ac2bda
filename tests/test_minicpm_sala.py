"""MiniCPM-SALA's structure at a toy size (`tiny-minicpm-sala-debug`: eight
layers `S L L L S L L S`, each an operator and then a dense FFN) against its
float32 reference (dynamo_tpu/models/reference/minicpm_sala.py): the serving
path's forward functions (a prompt in chunks, then decode through the pages,
the pooled-key sums and the state slots, across `dense_len`) on logits, XLA
twins and interpret-mode kernels alike; the selection alone against a brute
force; the pooled-key array; the Lightning recurrence in its three forms;
the masked chunk attention and the decode rows' tables; every control seen;
the refusals of `from_hf_config`. Tolerances: tests/minicpm_sala_common.py."""

import dataclasses
import filecmp
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dynamo_tpu.engine.kv_cache import KVCacheSpec, alloc_kv_pages
from dynamo_tpu.models import llama, loader
from dynamo_tpu.models.config import (LIGHTNING, PRESETS, SPARSE,
                                      ModelConfig)
from dynamo_tpu.models.reference import minicpm_sala as ref
from dynamo_tpu.ops import attention as att
from dynamo_tpu.ops import sparse_blocks as sb
from dynamo_tpu.ops import ssm as ssm_ops

from minicpm_sala_common import ATOL, RTOL, drawn, hf_dict, tiny

PS = 4       # page size == the pooled keys' stride
CHUNK = 16   # prompt chunk
REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
TOKENS = [int(t) for t in np.random.default_rng(0).integers(1, 500, 132)]
CUT = 88     # the prompt: chunks to here (dense_len 64 is crossed at chunk
#              5 of 6; the last chunk holds 8 real rows), then decode


def _jitted(fn):
    return jax.jit(fn, static_argnums=(0,), static_argnames=("page_size",))


prefill_chunk, decode_step, mixed_step = (
    _jitted(f) for f in (llama.prefill_chunk, llama.decode_step,
                         llama.mixed_step))


@pytest.fixture(scope="module")
def model():
    cfg = tiny()
    return cfg, drawn(cfg)


def _ref_cfg(cfg):
    return ref.Config.from_hf(hf_dict(cfg))


@pytest.fixture(scope="module")
def want(model):
    cfg, p = model
    members = []
    logits = ref.forward(_ref_cfg(cfg), ref.dequantize(p),
                         jnp.asarray(TOKENS), members=members)
    return logits, members


def _pools(cfg, slots=2, pages=64):
    spec = KVCacheSpec.from_model(cfg, num_pages=pages, page_size=PS,
                                  state_slots=slots, pooled_key_pages=40)
    return alloc_kv_pages(spec)


def _table(n_tokens, first_page=1):
    n = -(-n_tokens // PS)
    return jnp.concatenate([
        jnp.arange(first_page, first_page + n, dtype=jnp.int32),
        jnp.zeros((CHUNK // PS,), jnp.int32)])


def _run_program(cfg, p, tokens, cut, n_decode, slot=1, mixed=False):
    """The prompt's first `cut` tokens in 16-row chunks (the last padded),
    then `n_decode` decode steps in a batch of two slots of which the other
    is empty. `mixed`: the chunks ride llama.mixed_step beside an EMPTY
    decode batch. Returns ({position: logits}, k_pages, v_pages)."""
    kp, vp = _pools(cfg)
    table = _table(len(tokens))
    pages = llama.SlotPages(table, jnp.int32(slot))
    toks = jnp.asarray(tokens + [0] * CHUNK, jnp.int32)
    idle = (jnp.zeros((2,), jnp.int32), jnp.zeros((2,), jnp.int32),
            jnp.zeros((2, table.shape[0]), jnp.int32),
            jnp.ones((2,), jnp.int32))
    got, start = {}, 0
    while start < cut:
        n = min(CHUNK, cut - start)
        chunk = jnp.where(jnp.arange(CHUNK) < n, toks[start:start + CHUNK], 7)
        if mixed:
            out = mixed_step(cfg, p, *idle, chunk, jnp.int32(start),
                             jnp.int32(n), pages, kp, vp, page_size=PS)
            got[start + n - 1] = out.chunk_logits
        else:
            out = prefill_chunk(cfg, p, chunk, jnp.int32(start), jnp.int32(n),
                                kp, vp, pages, page_size=PS)
            got[start + n - 1] = out.last_logits
        kp, vp, start = out.k_pages, out.v_pages, start + n
    tables = jnp.zeros((2, table.shape[0]), jnp.int32).at[slot].set(table)
    for pos in range(cut, cut + n_decode):
        one = lambda v: jnp.zeros((2,), jnp.int32).at[slot].set(v)  # noqa
        out = decode_step(
            cfg, p, one(tokens[pos]), one(pos), tables,
            jnp.ones((2,), jnp.int32).at[slot].set(pos + 1), kp, vp,
            page_size=PS)
        kp, vp = out.k_pages, out.v_pages
        got[pos] = out.logits[slot]
    return got, kp, vp


@pytest.fixture(scope="module")
def program(model):
    return _run_program(*model, TOKENS, CUT, len(TOKENS) - CUT)


# -------------------------------------------------- the program's forwards --

def test_chunked_prefill_then_decode_matches_reference(program, want):
    """A prompt fed in chunks (the Lightning states handed from chunk to
    chunk through its slot, the keys and their page sums through its pages;
    chunks 1-4 attend densely, 5 and 6 select), then decode through all
    three across 44 more positions, against the reference's full forward."""
    got, _, _ = program
    assert sorted(got) == [15, 31, 47, 63, 79, 87] + list(range(88, 132))
    for pos, logits in got.items():
        np.testing.assert_allclose(logits, want[0][pos], rtol=RTOL,
                                   atol=ATOL, err_msg=str(pos))
    assert float(jnp.abs(want[0]).mean()) > 0.3  # loud enough to judge


def test_chunks_riding_mixed_steps_match_reference(model, want):
    got, _, _ = _run_program(*model, TOKENS, CUT, 4, mixed=True)
    for pos, logits in got.items():
        np.testing.assert_allclose(logits, want[0][pos], rtol=RTOL,
                                   atol=ATOL, err_msg=str(pos))


def test_kernels_in_interpret_mode_match_reference():
    """The same at 64-lane heads (a 128-lane row: no lane gate), the decode
    kernel over the (row, KV head) tables, the chunk kernel under dense_len
    and the state update's kernel in interpret mode."""
    cfg = tiny(head_dim=64, mamba_head_dim=64, ssm_state_size=64)
    p = drawn(cfg)
    tokens = TOKENS[:84]
    logits = ref.forward(_ref_cfg(cfg), ref.dequantize(p),
                         jnp.asarray(tokens))
    before = dict(att.pallas_fallback_counts())
    with att.attention_context("pallas_interpret", None, 1):
        got, _, _ = _run_program(cfg, p, tokens, 80, 4)
    assert dict(att.pallas_fallback_counts()) == before
    for pos, row in got.items():
        np.testing.assert_allclose(row, logits[pos], rtol=RTOL, atol=ATOL,
                                   err_msg=str(pos))


@pytest.mark.parametrize("variant", [v for v in ref.VARIANTS if v != "model"])
def test_each_control_is_seen(model, program, variant):
    """Every control of the reference (halved top-k, the pooled keys of the
    wrong page pair, no forced window, a slope of the wrong layer, the state
    in bf16) moves the logits past the tolerance the program is held to."""
    cfg, p = model
    got, _, _ = program
    wrong = ref.forward(_ref_cfg(cfg), ref.dequantize(p),
                        jnp.asarray(TOKENS), variant=variant)
    worst = max(float(jnp.max(jnp.abs(got[pos] - wrong[pos])
                              - ATOL - RTOL * jnp.abs(wrong[pos])))
                for pos in got)
    assert worst > 10 * ATOL, (variant, worst)


# ------------------------------------------------------------ the selection --

def _brute_select(q, k, n, sz, kernel):
    """One query q [H, D] at context n over keys k [n, KV, D], in numpy,
    block by block and pooled key by pooled key."""
    kvh, d = k.shape[1], k.shape[2]
    g = q.shape[0] // kvh
    nb = -(-n // sz.block)
    own = (n - 1) // sz.block
    out = np.zeros((kvh, nb), bool)
    if n <= sz.dense_len:
        out[:] = True
        return out
    nj = (n - kernel) // sz.stride + 1
    for kv in range(kvh):
        kbar = np.stack([k[j * sz.stride:j * sz.stride + kernel, kv].mean(0)
                         for j in range(nj)])
        s = q[kv * g:(kv + 1) * g] @ kbar.T / np.sqrt(d)
        pr = np.exp(s - s.max(-1, keepdims=True))
        r = (pr / pr.sum(-1, keepdims=True)).sum(0)
        score = np.full((nb,), -np.inf)
        for b in range(nb):
            for j in range(nj):
                if (j * sz.stride + kernel > b * sz.block
                        and j * sz.stride < (b + 1) * sz.block):
                    score[b] = max(score[b], r[j])
        forced = [b for b in range(nb) if b < sz.init_blocks
                  or own - sz.window_blocks < b <= own]
        rest = sorted((b for b in range(nb) if b not in forced),
                      key=lambda b: (-score[b], b))[:sz.topk]
        out[kv, forced + rest] = True
    return out


T_MAX = 272  # every case in arrays of one shape: one trace of each side


@jax.jit
def _select_jit(q, sums, n):
    cfg = tiny()
    return sb.select(q[None], sums[None], n[None], sb.sizes_of(cfg),
                     cfg.num_kv_heads)[0]


@jax.jit
def _ref_select_jit(q, k, n):
    rc = _ref_cfg(tiny())
    with jax.default_matmul_precision("highest"):
        return ref.select_blocks(rc, q[None], ref.pooled_keys(rc, k),
                                 n[None], T_MAX // rc.block_size)[0]


def _select_case(seed, n, tie=False):
    cfg = tiny()
    sz = sb.sizes_of(cfg)
    rng = np.random.default_rng(seed)
    kvh, d, h = cfg.num_kv_heads, cfg.head_dim, cfg.num_heads
    k = rng.standard_normal((n, kvh, d)).astype(np.float32) * 2.0
    q = rng.standard_normal((h, d)).astype(np.float32) * 2.0
    if tie:  # blocks 1..4 of KV head 0 alike: the lower indices win
        k[sz.block:5 * sz.block, 0] = k[sz.block:sz.block + 1, 0]
    rows = np.zeros((T_MAX, kvh, d), np.float32)
    rows[:n] = k
    sums = rows.reshape(T_MAX // PS, PS, kvh * d).sum(1)
    member = _select_jit(jnp.asarray(q), jnp.asarray(sums), jnp.int32(n))
    theirs = _ref_select_jit(jnp.asarray(q), jnp.asarray(rows), jnp.int32(n))
    return cfg, sz, q, k, np.asarray(member), np.asarray(theirs)


@pytest.mark.parametrize("n", [40, 64, 65, 66, 71, 80, 81, 96, 97, 130, 171,
                               255])
def test_selection_matches_a_brute_force(n):
    """Contexts at and under dense_len, just past it, ending inside a
    kernel, at a kernel's end, inside a block and at a block's end: the
    page-sum form of the pooled keys, the block maxima by reshaping and the
    comparison form of the top-k pick the brute force's blocks, and the
    reference's."""
    cfg, sz, q, k, member, theirs = _select_case(n, n)
    nb = -(-n // sz.block)
    want = _brute_select(q, k, n, sz, cfg.sparse_kernel_size)
    assert (member[:, :nb] == want).all(), (member[:, :nb], want)
    assert not member[:, nb:].any() and not theirs[:, nb:].any()
    if n > sz.dense_len:
        assert (member.sum(-1) == sz.picked).all()
    assert (theirs[:, :nb] == want).all()


def test_ties_go_to_the_lower_index():
    cfg, sz, q, k, member, theirs = _select_case(5, 200, tie=True)
    want = _brute_select(q, k, 200, sz, cfg.sparse_kernel_size)
    assert (member[:, :13] == want).all() and (theirs[:, :13] == want).all()
    # the tied blocks 2 and 3 score alike (block 1 and 4 share a pooled key
    # with their neighbours): never the higher without the lower
    assert not (member[0, 3] and not member[0, 2])


def test_forced_blocks_are_always_attended():
    _, sz, _, _, member, _ = _select_case(9, 230)
    own = (230 - 1) // sz.block
    assert member[:, 0].all() and member[:, own - 1:own + 1].all()
    assert (member.sum(-1) == sz.picked).all()


# ------------------------------------------------------------ pooled keys --

def test_pooled_key_sums_by_chunks_by_tokens_and_by_hand(model, program):
    """The slot's page sums after prefill by chunks and decode = after
    one-token writes alone = the sums of the keys its pages hold (a page
    part-filled: of its real rows alone), in the pages' order; the other
    slot's stay zero."""
    cfg, p = model
    _, kp, _ = program
    by_token, kp2, _ = _run_program(cfg, p, TOKENS[:40], 0, 40)
    table = np.asarray(_table(len(TOKENS)))[:len(TOKENS) // PS]
    for layer in range(3):
        pool, sums = kp.pages[layer], kp.pooled[0][layer, 1]
        np.testing.assert_allclose(
            sums[:len(table)], pool[table].sum(axis=1), rtol=1e-4, atol=1e-4)
        np.testing.assert_allclose(
            kp2.pooled[0][layer, 1, :10], sums[:10], rtol=1e-4, atol=1e-4)
        assert not np.asarray(kp.pooled[0][layer, 0]).any()
    assert float(jnp.abs(kp.pooled[0][:, 1, :len(table)]).mean()) > 0.1
    assert by_token  # decode from position 0: every page by tokens


def test_a_part_filled_page_sums_its_real_rows_only():
    sums = jnp.zeros((3, 5, 6), jnp.float32)  # [rows, pages, lanes]
    k = jnp.arange(8 * 2 * 3, dtype=jnp.float32).reshape(8, 2, 3)
    out = sb.page_sums_prefill(sums, k, 2, 8, 6, page_size=PS,
                               dtype=jnp.float32)
    flat = np.asarray(k).reshape(8, 6)
    np.testing.assert_allclose(out[2, 2], flat[:4].sum(0))
    np.testing.assert_allclose(out[2, 3], flat[4:6].sum(0))
    assert not np.asarray(out[:2]).any() and not np.asarray(out[2, :2]).any()
    pool = jnp.zeros((8, PS, 6)).at[5].set(jnp.asarray(flat[4:8]))
    tables = jnp.asarray([[0, 0, 0, 0, 0], [1, 2, 3, 5, 0]])
    again = sb.page_sums_token(
        out, pool, tables, jnp.asarray([0, 14]), jnp.asarray([False, True]),
        1, page_size=PS)
    np.testing.assert_allclose(again[2, 3], flat[4:7].sum(0))
    # the empty slot's row (a prompt's chunks may be filling it) is untouched
    assert (np.asarray(again[1]) == np.asarray(out[1])).all()


# ------------------------------------------------------ Lightning's forms --

def _lightning_case(t=48, h=4, d=8, seed=0):
    rng = np.random.default_rng(seed)
    q, k, v = (jnp.asarray(rng.standard_normal((t, h, d)), jnp.float32)
               for _ in range(3))
    a = -jnp.asarray(rng.uniform(0.02, 0.8, (h,)), jnp.float32)
    s = np.zeros((h, d, d), np.float32)  # [H, P (v), N (k)]
    ys = []
    for i in range(t):
        s = (np.exp(np.asarray(a))[:, None, None] * s
             + np.asarray(v[i])[:, :, None] * np.asarray(k[i])[:, None, :])
        ys.append(np.einsum("hpn,hn->hp", s, np.asarray(q[i])))
    return q, k, v, a, np.stack(ys), s


@pytest.mark.parametrize("chunk", [4, 16, 48])
def test_lightning_through_scan_chunked_is_the_loop(chunk):
    q, k, v, a, ys, s = _lightning_case()
    t, h, d = q.shape
    y, final = ssm_ops.scan_chunked(
        v, jnp.ones((t, h)), a, k, q, jnp.zeros((h,)),
        jnp.zeros((h, d, d)), chunk)
    np.testing.assert_allclose(y, ys, rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(final, s, rtol=1e-4, atol=1e-4)


def test_lightning_through_step_is_the_loop_and_padding_leaves_the_state():
    q, k, v, a, ys, s = _lightning_case()
    t, h, d = q.shape
    state = jnp.zeros((1, h, d, d))
    for i in range(t):
        y, state = ssm_ops.step(v[i][None], jnp.ones((1, h)), a, k[i][None],
                                q[i][None], jnp.zeros((h,)), state)
        np.testing.assert_allclose(y[0], ys[i], rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(state[0], s, rtol=1e-4, atol=1e-4)
    _, same = ssm_ops.step(v[:1], jnp.zeros((1, h)), a, k[:1], q[:1],
                           jnp.zeros((h,)), state)
    assert (same == state).all()  # dt = 0: bit for bit


def test_a_bf16_state_fails_the_tolerance():
    q, k, v, a, ys, _ = _lightning_case(t=200)
    t, h, d = q.shape
    state = jnp.zeros((1, h, d, d), jnp.float32)
    worst = 0.0
    for i in range(t):
        y, state = ssm_ops.step(v[i][None], jnp.ones((1, h)), a, k[i][None],
                                q[i][None], jnp.zeros((h,)), state)
        state = state.astype(jnp.bfloat16).astype(jnp.float32)
        worst = max(worst, float(jnp.max(jnp.abs(y[0] - ys[i]))))
    assert worst > 20 * ATOL


def test_slopes_are_the_published_formula(model):
    cfg, _ = model
    got = np.asarray(llama.lightning_slopes(cfg))
    assert got.shape == (8, 8)
    np.testing.assert_allclose(got[0, 7], 2.0 ** -8 * (1 + 1e-5), rtol=1e-6)
    np.testing.assert_allclose(got[7, 0], 2.0 ** -1 * (
        1 - 7 / (7 + 1e-5) + 1e-5), rtol=1e-4)
    for layer in range(8):
        np.testing.assert_allclose(
            got[layer], ref.slopes(_ref_cfg(cfg), layer), rtol=1e-6)


# ---------------------------------------- the attention under a selection --

def _attention_case(n_ctx, c, seed=1):
    cfg = tiny()
    sz = sb.sizes_of(cfg)
    rng = np.random.default_rng(seed)
    kvh, d, h = cfg.num_kv_heads, cfg.head_dim, cfg.num_heads
    pages = 200
    kp = jnp.asarray(rng.standard_normal((pages, PS, kvh * d)), jnp.float32)
    vp = jnp.asarray(rng.standard_normal((pages, PS, kvh * d)), jnp.float32)
    table = jnp.asarray(rng.permutation(np.arange(1, pages))[:-(-n_ctx // PS)
                                                             + 4], jnp.int32)
    q = jnp.asarray(rng.standard_normal((c, h, d)), jnp.float32)
    return cfg, sz, q, kp, vp, table


def _masked_by_hand(q, kp, vp, table, qpos, member, sz, kvh):
    """Attention of each query over the rows of its member blocks, dense
    numpy."""
    d = q.shape[-1]
    k = np.asarray(kp)[np.asarray(table)].reshape(-1, kvh, d)
    v = np.asarray(vp)[np.asarray(table)].reshape(-1, kvh, d)
    out = np.zeros(q.shape, np.float32)
    g = q.shape[1] // kvh
    for i, pos in enumerate(qpos):
        rows = np.repeat(np.asarray(member[i]), sz.block, axis=-1)
        rows = rows[:, :k.shape[0]] & (np.arange(k.shape[0]) <= pos)[None]
        for hd in range(q.shape[1]):
            kv = hd // g
            s = np.asarray(q[i, hd]) @ k[:, kv].T / np.sqrt(d)
            s = np.where(rows[kv, :s.shape[0]], s, -np.inf)
            pr = np.exp(s - s.max())
            out[i, hd] = (pr / pr.sum()) @ v[:, kv]
    return out


@pytest.mark.parametrize("start", [48, 64, 240])
def test_masked_chunk_attention_is_attention_over_the_members(start):
    """A chunk that ends under dense_len, one that starts at it and one
    deep in a context of two tiles: every query over its own blocks'."""
    c = 16
    cfg, sz, q, kp, vp, table = _attention_case(start + c, c)
    sums = kp.sum(axis=1)[table]  # the sequence's pages, in their order
    o, seen, skipped = sb.chunk_attention(
        q, kp, vp, sums[None], table, jnp.int32(start), sz, page_size=PS,
        num_kv_heads=cfg.num_kv_heads)
    contexts = start + 1 + jnp.arange(c)
    member = sb.select(q, sums[None], contexts, sz, cfg.num_kv_heads)
    want = _masked_by_hand(q, kp, vp, table, start + np.arange(c), member,
                           sz, cfg.num_kv_heads)
    np.testing.assert_allclose(o, want, rtol=2e-4, atol=2e-4)
    tiles = (start + c - 1) // (sb.TILE_BLOCKS * sz.block) + 1
    assert int(seen) + int(skipped) == tiles and int(seen) >= 1


def test_a_tile_nobody_selected_is_walked_past():
    c = 16
    cfg, sz, q, kp, vp, table = _attention_case(600, c)
    start = 560
    member = jnp.zeros((c, 2, 40), bool).at[:, :, 0].set(True).at[
        :, :, 35].set(True)  # tiles 0 and 2 of three
    o, seen, skipped = sb.masked_chunk_attention(
        q, kp, vp, table, jnp.int32(start), member, page_size=PS,
        block=sz.block, num_kv_heads=2)
    assert (int(seen), int(skipped)) == (2, 1)
    want = _masked_by_hand(q, kp, vp, table, start + np.arange(c), member, sz,
                           2)
    np.testing.assert_allclose(o, want, rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("contexts", [(130, 1), (64, 200), (65, 97)])
def test_decode_rows_attend_their_selected_blocks(contexts):
    """Rows past dense_len beside rows at or under it (and an empty slot's
    context 1) in one call: each attends its members' rows, each KV head by
    a table of its own."""
    b = len(contexts)
    cfg, sz, q, kp, vp, table = _attention_case(max(contexts), b, seed=4)
    tables = jnp.stack([table, jnp.roll(table, 3)])[:b]
    sums = kp.sum(axis=1)[tables]  # each row's pages, in their order
    ctx = jnp.asarray(contexts, jnp.int32)
    o = sb.decode_attention(q, kp, vp, sums, tables, ctx, None, sz,
                            page_size=PS, num_kv_heads=cfg.num_kv_heads)
    scores = sb.block_scores(q, sums, ctx, sz, cfg.num_kv_heads)
    member = sb.members(scores, ctx, sz)
    picked = sb.members(scores, ctx, sz, picks=True)
    for i in range(b):
        want = _masked_by_hand(q[i:i + 1], kp, vp, tables[i],
                               [contexts[i] - 1], member[i:i + 1], sz,
                               cfg.num_kv_heads)
        np.testing.assert_allclose(o[i], want[0], rtol=2e-4, atol=2e-4)
    vt, lens, sparse = sb.decode_views(picked, tables, ctx, sz, PS)
    assert vt.shape == (b, 2, max(sz.picked * sz.block, sz.dense_len) // PS)
    for i, n in enumerate(contexts):
        assert bool(sparse[i]) == (n > sz.dense_len)
        assert int(lens[i]) == (n if n <= sz.dense_len else
                                (sz.picked - 1) * sz.block + (n - 1)
                                % sz.block + 1)


# ------------------------------------------------- configuration and pools --

def test_the_pools_of_the_tiny_preset():
    cfg = tiny()
    with pytest.raises(ValueError, match="pooled_key_pages"):
        KVCacheSpec.from_model(cfg, num_pages=32, page_size=PS, state_slots=3)
    spec = KVCacheSpec.from_model(cfg, num_pages=32, page_size=PS,
                                  state_slots=3, pooled_key_pages=20)
    assert (spec.num_layers, spec.state_layers) == (3, 5)
    assert spec.state_stacked and spec.ssm_shape == (8, 32, 32)
    assert spec.conv_shape == () and spec.pooled_key_shape == (3, 3, 20, 64)
    assert spec.bytes_per_slot() == 5 * 8 * 32 * 32 * 4
    assert spec.pooled_key_bytes() == 3 * 3 * 20 * 64 * 4
    assert not spec.state_kept_at_blocks  # 160 KB a slot, 3 KB a page
    kp, vp = alloc_kv_pages(spec)
    assert kp.state[0].shape == (5, 3, 8, 32, 32) and vp.state == ()
    assert kp.pooled[0].shape == (3, 3, 20, 64) and vp.pooled == ()


def test_from_hf_config_on_the_catalog_row():
    row = [json.loads(ln) for ln in open(CATALOG)
           if '"MiniCPM-SALA"' in ln][0]
    cfg = ModelConfig.from_hf_config(row["config"], name="catalog")
    assert cfg.mixer_types.count(SPARSE) == 8
    assert cfg.mixer_types.count(LIGHTNING) == 24
    assert [i for i, k in enumerate(cfg.mixer_types) if k == SPARSE] == [
        0, 9, 16, 17, 22, 29, 30, 31]
    assert (cfg.hidden_size, cfg.num_heads, cfg.num_kv_heads, cfg.head_dim,
            cfg.intermediate_size, cfg.vocab_size) == (4096, 32, 2, 128,
                                                       16384, 73448)
    assert (cfg.scale_emb, cfg.scale_depth, cfg.dim_model_base) == (
        12.0, 1.4, 256)
    assert sb.sizes_of(cfg) == sb.Sizes(16, 64, 64, 1, 32, 8192)
    assert cfg.sparse_picked_blocks == 97 and cfg.is_sala
    assert cfg.operator_ffn and not cfg.conv_state and not cfg.is_moe
    assert not cfg.tie_word_embeddings and cfg.rms_norm_eps == 1e-6
    published = os.path.join(
        REPO, "benchmarks/chip/configs/minicpm-sala-w8a8-1chip")
    served = ModelConfig.from_model_name(published)
    assert dataclasses.replace(served, name="catalog") == cfg
    with open(os.path.join(published, "config.json")) as f:
        stated = json.load(f)
    assert {k: stated[k] for k in row["config"]} == row["config"]
    # sparse_config: ASSUMED sizes; layer_types: a copy of mixer_types that
    # makes a tree without the model refuse the file by name, at once
    assert set(stated) - set(row["config"]) == {"sparse_config",
                                                "layer_types"}
    assert stated["layer_types"] == stated["mixer_types"]


def test_the_tiny_preset_is_the_published_structure(model):
    cfg, _ = model
    assert ModelConfig.from_hf_config(
        hf_dict(cfg), name=cfg.name, dtype="float32") == dataclasses.replace(
        cfg, ssm_chunk_size=128, max_position_embeddings=8192)


@pytest.mark.parametrize("key,value,word", [
    ("attn_use_rope", True, "attn_use_rope"),
    ("lightning_use_rope", False, "lightning_use_rope"),
    ("qk_norm", False, "qk_norm"),
    ("use_output_gate", False, "use_output_gate"),
    ("use_output_norm", False, "use_output_norm"),
    ("attn_use_output_gate", False, "attn_use_output_gate"),
    ("attention_bias", True, "attention_bias"),
    ("lightning_scale", "1", "lightning_scale"),
    ("lightning_nkv", 2, "lightning_nkv"),
    ("lightning_head_dim", 16, "lightning_nh"),
    ("rope_scaling", {"type": "yarn", "factor": 4}, "rope_scaling"),
    ("mixer_types", ["minicpm4"] * 7 + ["mamba"], "mixer_types"),
    ("sparse_config", {"kernel_size": 12}, "sparse_kernel_size"),
    ("sparse_config", {"pool": "max"}, "sparse_config"),
])
def test_from_hf_config_refuses_by_name(key, value, word):
    hf = hf_dict(tiny())
    hf[key] = value
    with pytest.raises(ValueError, match=word):
        ModelConfig.from_hf_config(hf, name="x")


def test_a_checkpoint_is_refused_by_name():
    with pytest.raises(NotImplementedError, match="minicpm_sala"):
        loader.load_hf_safetensors(tiny(), [])


def test_the_page_size_must_be_the_stride():
    with pytest.raises(ValueError, match="page_size=16"):
        sb.check_page_size(tiny(), 16)
    sb.check_page_size(tiny(), 4)


def test_the_benchmark_keeps_the_reference_byte_for_byte():
    assert filecmp.cmp(
        os.path.join(REPO, "dynamo_tpu/models/reference/minicpm_sala.py"),
        os.path.join(REPO, "benchmarks/chip/reference/minicpm_sala.py"),
        shallow=False)


def test_random_quantized_params_cover_the_tree():
    cfg = dataclasses.replace(PRESETS["tiny-minicpm-sala-debug"])
    p = loader.random_quantized_params(cfg, seed=5, mode="w8a8")
    assert set(p) == set(llama.param_specs(cfg))
    quantized = {k for k, v in p.items() if hasattr(v, "q")}
    assert {"w_q", "lightning.w_k", "w_og", "lightning.w_og", "wo", "w_down",
            "embed", "lm_head"} <= quantized
    assert "lightning.out_norm" not in quantized
