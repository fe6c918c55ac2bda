"""Unified ragged paged-attention step suite (make rpa-check, marker `rpa`).

Three layers, mirroring the subsystem (docs/perf.md "Unified ragged step"):

- op level: the Pallas ragged kernel (interpret mode) against the XLA
  composition (decode gather + chunk gather) on mixed batches whose
  mid-prefill rows cross page boundaries, bf16-pool and int8-packed;
- engine level (enforce_eager, cheap for tier-1): the mixed step's greedy
  outputs are token-identical to the classic chunk/decode alternation —
  plain, LoRA-mixed, under preemption/recovery, and with namespaced
  prefix-cache hits re-entering mid-chunk;
- acceptance (jitted, marker `slow`, still run by `make rpa-check` /
  `make test-full`): the same identity through the donated jit programs,
  LoRA and int8-KV included, plus the prefill_interference bench contract.
"""

import os

import numpy as np
import pytest

from dynamo_tpu.engine.config import EngineConfig
from dynamo_tpu.engine.engine import Engine
from dynamo_tpu.engine.request import GenRequest

pytestmark = pytest.mark.rpa

PROMPT = [(i * 11) % 300 + 1 for i in range(50)]


def _mk(mixed, **kw):
    base = dict(model="tiny-debug", page_size=4, num_pages=256,
                max_num_seqs=4, max_seq_len=256, enforce_eager=True,
                prefill_chunk_tokens=8, mixed_batch_tokens=mixed)
    base.update(kw)
    return Engine(EngineConfig(**base))


def _collect(out, evs):
    for ev in evs:
        if ev.token_id >= 0:
            out.setdefault(ev.request_id, []).append(ev.token_id)


def _interference(eng, prompt=None, live_tokens=10, long_tokens=4):
    """A live greedy stream + a long prompt arriving mid-decode: the shape
    that exercises the mixed step (or the classic alternation when off).
    EVERY step's events are collected — the two A/B arms admit on different
    steps, so dropping warm-up events would skew one arm's token list."""
    out = {"live": [], "long": []}
    eng.add_request(GenRequest("live", [1, 2, 3], max_tokens=live_tokens,
                               temperature=0.0, ignore_eos=True))
    for _ in range(3):
        _collect(out, eng.step())
    eng.add_request(GenRequest("long", prompt or PROMPT,
                               max_tokens=long_tokens,
                               temperature=0.0, ignore_eos=True))
    while eng.has_work:
        _collect(out, eng.step())
    return out


# ------------------------------------------------------------- op parity --


def _mixed_inputs(rng, quantized, ps=16, n_pool=64, b=3, h=8, n_kv=2, d=64,
                  pmax=6, c=32, start=16, wp=5):
    """Mixed ragged batch whose rows cross page boundaries: a 1-token
    context, a mid-page context (2 pages + 5), a full-table context, plus a
    32-token chunk starting mid-prompt at token 16 (page 1)."""
    import jax.numpy as jnp

    from dynamo_tpu.ops import attention as att

    kf = rng.normal(size=(n_pool * ps, n_kv, d)).astype(np.float32)
    vf = rng.normal(size=(n_pool * ps, n_kv, d)).astype(np.float32)
    if quantized:
        w = att.kv_lane_width(n_kv, d, True)
        kp = att.pack_kv_rows(jnp.asarray(kf), w).reshape(n_pool, ps, w)
        vp = att.pack_kv_rows(jnp.asarray(vf), w).reshape(n_pool, ps, w)
    else:
        kp = jnp.asarray(kf.reshape(n_pool, ps, n_kv * d))
        vp = jnp.asarray(vf.reshape(n_pool, ps, n_kv * d))
    q = jnp.asarray(rng.normal(size=(b + c, h, d)), jnp.float32)
    # disjoint non-zero page ids per sequence; trash-padded tails
    tables = np.zeros((b, pmax), np.int32)
    tables[0, :1] = [1]
    tables[1, :3] = [2, 3, 4]
    tables[2, :pmax] = np.arange(10, 10 + pmax)
    ctx = jnp.asarray([1, 2 * ps + 5, ps * pmax], jnp.int32)
    p_pages = jnp.asarray([20, 21, 22, 23, 24][:wp], jnp.int32)
    return q, kp, vp, jnp.asarray(tables), ctx, p_pages, start


@pytest.mark.parametrize("quantized", [False, True],
                         ids=["f32_pool", "int8_pool"])
def test_ragged_kernel_matches_xla_composition(quantized):
    """The Pallas ragged kernel (interpret mode) is numerically equivalent
    to the per-path reference composition on a mixed batch with
    page-boundary-crossing mid-prefill rows — bf16 and int8-packed pools."""
    from dynamo_tpu.ops import attention as att

    rng = np.random.default_rng(17)
    q, kp, vp, tabs, ctx, pp, start = _mixed_inputs(rng, quantized)

    def run(backend):
        with att.attention_context(backend, None):
            return att.ragged_mixed_attention(
                q, kp, vp, tabs, ctx, pp, start, page_size=16,
                num_kv_heads=2, num_decode=3)

    ref = run("xla")
    out = run("pallas_interpret")
    assert not np.any(np.isnan(np.asarray(out)))
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)


def test_ragged_kernel_follows_the_scoped_backend(monkeypatch):
    """The ragged dispatch follows the engine's scoped attention backend
    and nothing else: a kernel backend calls the kernel, `xla` the
    composition."""
    from dynamo_tpu.ops import attention as att
    from dynamo_tpu.ops import ragged_attention as ra

    rng = np.random.default_rng(3)
    q, kp, vp, tabs, ctx, pp, start = _mixed_inputs(rng, False)

    calls = []
    real = ra.ragged_paged_attention

    def spy(*a, **k):
        calls.append(1)
        return real(*a, **k)

    monkeypatch.setattr(ra, "ragged_paged_attention", spy)
    for backend, kernel in (("pallas_interpret", True), ("xla", False)):
        del calls[:]
        with att.attention_context(backend, None):
            att.ragged_mixed_attention(q, kp, vp, tabs, ctx, pp, start,
                                       page_size=16, num_kv_heads=2,
                                       num_decode=3)
        assert bool(calls) is kernel


def _poisoned_cell(rng, start, k1=1):
    """The cells' mixed step in small: 32 slots of which 5 live, 32/8
    heads, one 16-token chunk at `start`. Returns the clean operands and a
    poisoned twin: NaN in every pool page that no row reaches below its
    kv_len, and every table entry past a row's live pages naming one."""
    import jax.numpy as jnp

    ps, n_pool, b, h, n_kv, d, width, c = 4, 96, 32, 32, 8, 16, 16, 16
    kf = rng.normal(size=(n_pool, ps, n_kv * d)).astype(np.float32)
    vf = rng.normal(size=(n_pool, ps, n_kv * d)).astype(np.float32)
    live = {1: 5, 7: 33, 12: ps * width - k1 + 1, 20: 17, 31: 48}
    tables = np.zeros((b, width), np.int32)
    ctx = np.ones((b,), np.int32)  # the inactive-slot contract
    n_live_pages = np.ones((b,), np.int32)
    nxt = 1
    for slot, n_tok in live.items():
        # a verify window's horizon is its last draft: k1 - 1 tokens more
        n = -(-(n_tok + k1 - 1) // ps)
        tables[slot, :n] = np.arange(nxt, nxt + n)
        ctx[slot], n_live_pages[slot] = n_tok, n
        nxt += n
    wp = (start + c) // ps + 3
    p_pages = np.zeros((wp,), np.int32)
    p_pages[:(start + c) // ps] = np.arange(nxt, nxt + (start + c) // ps)
    nxt += (start + c) // ps
    dead = n_pool - 1
    assert nxt < dead
    bad_k, bad_v = kf.copy(), vf.copy()
    bad_k[nxt:], bad_v[nxt:] = np.nan, np.nan
    bad_tables, bad_pages = tables.copy(), p_pages.copy()
    for slot in range(b):
        bad_tables[slot, n_live_pages[slot]:] = dead
    bad_pages[(start + c) // ps:] = dead
    q = jnp.asarray(rng.normal(size=(b * k1 + c, h, d)), jnp.float32)
    clean = (q, jnp.asarray(kf), jnp.asarray(vf), jnp.asarray(tables),
             jnp.asarray(ctx), jnp.asarray(p_pages), start)
    bad = (q, jnp.asarray(bad_k), jnp.asarray(bad_v),
           jnp.asarray(bad_tables), jnp.asarray(ctx),
           jnp.asarray(bad_pages), start)
    return clean, bad, dict(page_size=ps, num_kv_heads=n_kv)


@pytest.mark.parametrize("start,k1", [(0, 1), (32, 1), (32, 3)],
                         ids=["first_chunk", "mid_prompt", "verify_k3"])
def test_ragged_kernel_attends_live_kv_only(start, k1):
    """What PR 26 made the default does work in proportion to the live KV:
    with every dead page and every table entry past a row's live pages
    poisoned, the kernel's output is finite and equal to the composition's
    on the clean pool, while the composition itself, which gathers the
    whole table, turns NaN on the poisoned one."""
    from dynamo_tpu.ops import attention as att

    clean, bad, kw = _poisoned_cell(np.random.default_rng(26), start, k1)

    def run(backend, args):
        q, kp, vp, tabs, ctx, pp, st = args
        with att.attention_context(backend, None):
            if k1 == 1:
                return np.asarray(att.ragged_mixed_attention(
                    *args, num_decode=32, **kw))
            return np.asarray(att.ragged_verify_attention(
                q, kp, vp, tabs, ctx - 1, pp, st, num_verify=32,
                verify_width=k1, **kw))

    ref = run("xla", clean)
    out = run("pallas_interpret", bad)
    assert np.all(np.isfinite(out))
    np.testing.assert_allclose(out, ref, atol=2e-5, rtol=2e-5)
    assert np.isnan(run("xla", bad)).any()  # the poison is in reach of a gather


def test_ragged_gate_demotion_is_counted():
    """A lane-gate demotion (64-lane KV span, below the 128-lane minimum)
    lands in pallas_fallback_counts under ("ragged attention", ...) — the
    series dynamo_pallas_fallback_total exposes (observability satellite)."""
    import jax.numpy as jnp

    from dynamo_tpu.ops import attention as att

    rng = np.random.default_rng(5)
    ps, n_kv, d, h = 4, 2, 32, 4  # span 64: fails the lane gate
    kp = jnp.asarray(rng.normal(size=(16, ps, n_kv * d)), jnp.float32)
    q = jnp.asarray(rng.normal(size=(2 + 4, h, d)), jnp.float32)
    tabs = jnp.asarray([[1, 0], [2, 3]], jnp.int32)
    ctx = jnp.asarray([2, 7], jnp.int32)
    pp = jnp.asarray([4, 5], jnp.int32)
    before = dict(att.pallas_fallback_counts())
    with att.attention_context("pallas_interpret", None):
        out = att.ragged_mixed_attention(q, kp, kp, tabs, ctx, pp, 0,
                                         page_size=ps, num_kv_heads=n_kv,
                                         num_decode=2)
    assert out.shape == q.shape
    after = att.pallas_fallback_counts()
    ragged_keys = [k for k in after if k[0] == "ragged attention"
                   and after[k] > before.get(k, 0)]
    assert ragged_keys, f"no ragged demotion counted: {after}"


# ---------------------------------------- live work only (PR 45's kernel) --
# One grid step a query block and a loop over that block's own KV blocks; a
# decode row handed kv_len 0 (`kernel_lens`, as llama.mixed_step hands an
# empty slot) owns none: no page copy, zeros written, and the ring's issue
# cursor steps over it.

_PS, _SLOTS, _WIDTH, _CHUNK = 4, 6, 24, 16  # 8 pages = 32 tokens a KV block
# contexts: one ends exactly on a KV block (32), one a token past it, one
# on the second block's end, a single token, two mid-block
_CTX = (45, 32, 33, 64, 1, 70)
# which slots hold a sequence
_LIVE = {"empty_first": (2, 3, 4, 5), "empty_middle": (0, 1, 4, 5),
         "empty_last": (0, 1, 2, 3), "first_and_last_live": (0, 5),
         "all_empty": (), "all_live": (0, 1, 2, 3, 4, 5)}


def _live_work_cell(rng, kind, live, start, k1=1):
    """6 slots of which `live` hold a sequence, one 16-token chunk at
    `start`. kind: plain (8 / 2 heads of 64, float32 pool) | shared (MLA: 4
    heads on ONE 128-lane row, the V pool without lanes) | int8 (packed).
    Returns the clean operands, a poisoned twin (NaN in the trash page, in
    every free page and named by every table entry past a row's horizon;
    None for int8) and the ops' keywords."""
    import jax.numpy as jnp

    from dynamo_tpu.ops import attention as att

    h, n_kv, d = (4, 1, 128) if kind == "shared" else (8, 2, 64)
    chunk_pages = (start + _CHUNK) // _PS
    n_pool = 1 + sum(-(-(c + k1 - 1) // _PS) for c in _CTX) + chunk_pages + 2
    kf = rng.normal(size=(n_pool, _PS, n_kv * d)).astype(np.float32)
    vf = rng.normal(size=(n_pool, _PS, n_kv * d)).astype(np.float32)
    tables = np.zeros((_SLOTS, _WIDTH), np.int32)
    ctx = np.ones((_SLOTS,), np.int32)  # the engine's pin of an empty slot
    dead = n_pool - 1
    bad_tables = np.full((_SLOTS, _WIDTH), dead, np.int32)
    bad_tables[:, 0] = 0  # an empty slot's first page is the trash page
    nxt = 1
    for slot in live:
        n = -(-(_CTX[slot] + k1 - 1) // _PS)
        tables[slot, :n] = bad_tables[slot, :n] = np.arange(nxt, nxt + n)
        ctx[slot] = _CTX[slot]
        nxt += n
    pages = np.zeros((chunk_pages + 3,), np.int32)
    pages[:chunk_pages] = np.arange(nxt, nxt + chunk_pages)
    bad_pages = np.where(np.arange(pages.size) < chunk_pages, pages, dead)
    nxt += chunk_pages
    q = jnp.asarray(rng.normal(size=(_SLOTS * k1 + _CHUNK, h, d)),
                    jnp.float32)

    def pools(k, v):
        if kind == "int8":
            w = att.kv_lane_width(n_kv, d, True)
            return tuple(att.pack_kv_rows(
                jnp.asarray(x.reshape(-1, n_kv, d)), w).reshape(n_pool, _PS, w)
                for x in (k, v))
        if kind == "shared":
            return jnp.asarray(k), jnp.zeros((n_pool, _PS, 0), jnp.float32)
        return jnp.asarray(k), jnp.asarray(v)

    clean = (q, *pools(kf, vf), jnp.asarray(tables), jnp.asarray(ctx),
             jnp.asarray(pages), start)
    bad = None
    if kind != "int8":
        bad_k, bad_v = kf.copy(), vf.copy()
        bad_k[0], bad_v[0] = np.nan, np.nan
        bad_k[nxt:], bad_v[nxt:] = np.nan, np.nan
        bad = (q, *pools(bad_k, bad_v), jnp.asarray(bad_tables),
               jnp.asarray(ctx), jnp.asarray(bad_pages), start)
    return clean, bad, dict(page_size=_PS, num_kv_heads=n_kv)


def _mixed(backend, args, live, window=0, **kw):
    """ragged_mixed_attention as llama.mixed_step hands it over
    (`kernel_lens`: 0 where the slot is empty); numpy."""
    import jax.numpy as jnp

    from dynamo_tpu.ops import attention as att

    mask = np.zeros((_SLOTS,), bool)
    mask[list(live)] = True
    with att.attention_context(backend, None):
        return np.asarray(att.ragged_mixed_attention(
            *args, num_decode=_SLOTS, window=window or None,
            kernel_lens=jnp.where(jnp.asarray(mask), args[4], 0), **kw))


def _rows(live):
    """The mask of the rows somebody owns: live decode rows, the chunk."""
    rows = np.zeros((_SLOTS + _CHUNK,), bool)
    rows[list(live)] = True
    rows[_SLOTS:] = True
    return rows


@pytest.mark.parametrize("pattern", sorted(_LIVE))
@pytest.mark.parametrize("window", [0, 12], ids=["full", "window12"])
@pytest.mark.parametrize("kind", ["plain", "shared", "int8"])
def test_ragged_kernel_skips_empty_rows(kind, window, pattern):
    """Live rows and the chunk's rows are the XLA composition's, an empty
    row reads zero, wherever the empty rows lie in the batch: first, in
    the middle, last, all of them, none."""
    live = _LIVE[pattern]
    # all_empty: the chunk at start 0 is the only owner; empty_first: it
    # ends exactly on a KV block (16 + 16)
    start = {"all_empty": 0, "empty_first": 16}.get(pattern, 40)
    clean, _, kw = _live_work_cell(np.random.default_rng(45), kind, live,
                                   start)
    ref = _mixed("xla", clean, live, window, **kw)
    out = _mixed("pallas_interpret", clean, live, window, **kw)
    rows = _rows(live)
    tol = 2e-2 if kind == "int8" else 2e-5
    np.testing.assert_allclose(out[rows], ref[rows], atol=tol, rtol=tol)
    assert not out[~rows].any()
    assert np.isfinite(ref[~rows]).all()  # the twin keeps the engine's pin


@pytest.mark.parametrize("window", [0, 12], ids=["full", "window12"])
@pytest.mark.parametrize("kind", ["plain", "shared", "int8"])
def test_ragged_kernel_with_no_decode_row(kind, window):
    """num_decode = 0 (a windowed chunk alone takes this form): the chunk
    is the only sequence, at start 0 and mid-prompt."""
    from dynamo_tpu.ops import attention as att

    for start in (0, 40):
        (q, kp, vp, _, _, pages, _), _, kw = _live_work_cell(
            np.random.default_rng(46), kind, (), start)
        args = (q[_SLOTS:], kp, vp, np.zeros((0, _WIDTH), np.int32),
                np.zeros((0,), np.int32), pages, start)

        def run(backend):
            with att.attention_context(backend, None):
                return np.asarray(att.ragged_mixed_attention(
                    *args, num_decode=0, window=window or None, **kw))

        tol = 2e-2 if kind == "int8" else 2e-5
        np.testing.assert_allclose(run("pallas_interpret"), run("xla"),
                                   atol=tol, rtol=tol)


@pytest.mark.parametrize("pattern", ["empty_middle", "all_empty"])
@pytest.mark.parametrize("kind", ["plain", "shared", "int8"])
def test_ragged_verify_windows_keep_their_blocks(kind, pattern):
    """decode_q = K + 1: an inactive window (zero table, position 0) has
    kv_len K + 1 and keeps its block on the trash page, so every row is
    the XLA composition's, the inactive windows' too."""
    from dynamo_tpu.ops import attention as att

    k1, live = 3, _LIVE[pattern]
    (q, kp, vp, tabs, ctx, pages, start), _, kw = _live_work_cell(
        np.random.default_rng(47), kind, live, 40, k1)

    def run(backend):
        with att.attention_context(backend, None):
            return np.asarray(att.ragged_verify_attention(
                q, kp, vp, tabs, ctx - 1, pages, start, num_verify=_SLOTS,
                verify_width=k1, **kw))

    tol = 2e-2 if kind == "int8" else 2e-5
    np.testing.assert_allclose(run("pallas_interpret"), run("xla"),
                               atol=tol, rtol=tol)


@pytest.mark.parametrize("pattern", ["empty_middle", "first_and_last_live",
                                     "all_empty"])
@pytest.mark.parametrize("window", [0, 12], ids=["full", "window12"])
@pytest.mark.parametrize("kind", ["plain", "shared"])
def test_ragged_kernel_reads_nothing_nobody_owns(kind, window,
                                                 pattern):
    """NaN in the trash page, in every free page and behind every table
    entry past a row's horizon: live rows and the chunk's rows bit for bit
    what they are on the clean pool, empty rows zero."""
    live = _LIVE[pattern]
    clean, bad, kw = _live_work_cell(np.random.default_rng(48), kind, live,
                                     40)
    want = _mixed("pallas_interpret", clean, live, window, **kw)
    got = _mixed("pallas_interpret", bad, live, window, **kw)
    rows = _rows(live)
    assert np.isfinite(want).all()
    np.testing.assert_array_equal(got[rows], want[rows])
    assert not got[~rows].any()
    # the poison is in reach of anything that follows a table or the pin
    assert np.isnan(_mixed("xla", bad, live, window, **kw)).any()


@pytest.mark.parametrize("window", [0, 12], ids=["full", "window12"])
@pytest.mark.parametrize("kind", ["plain", "shared", "int8"])
def test_ragged_kernel_rows_do_not_talk_through_the_ring(kind,
                                                         window):
    """A live row's output, and the chunk's, are bit for bit what the same
    call gives with every other slot emptied: the ring that runs on across
    query blocks hands each block its own pages in its own order, whoever
    else owns blocks before or after it."""
    live = _LIVE["all_live"]
    clean, _, kw = _live_work_cell(np.random.default_rng(49), kind, live, 40)
    full = _mixed("pallas_interpret", clean, live, window, **kw)
    for slot in live:
        alone = _mixed("pallas_interpret", clean, (slot,),
                       window, **kw)
        rows = _rows((slot,))
        np.testing.assert_array_equal(alone[rows], full[rows])
        assert not alone[~rows].any()


# -------------------------------------------------- engine mixed (eager) --


def test_mixed_config_normalization():
    """mixed_batch_tokens page-aligns at init and an unset chunk size
    inherits the budget (mixed implies chunked prefill)."""
    eng = _mk(10, prefill_chunk_tokens=0)
    assert eng.cfg.mixed_batch_tokens == 12  # ceil(10/4)*4
    assert eng.cfg.prefill_chunk_tokens == 12


def test_mixed_step_matches_classic_greedy():
    """Tentpole identity: live stream + long prompt through the unified
    ragged step produce exactly the classic chunk/decode tokens, and the
    mixed path actually ran (mixed_step phase + composition stats)."""
    classic = _interference(_mk(0), prompt=PROMPT[:32])
    eng = _mk(8)
    mixed = _interference(eng, prompt=PROMPT[:32])
    assert mixed == classic
    assert eng.metrics.mixed_count >= 3
    snap = eng.metrics.snapshot()
    assert snap["phases"]["mixed_step"]["count"] == eng.metrics.mixed_count
    assert 0.0 < snap["mixed_frac_mean"] <= 1.0


def test_mixed_idle_engine_single_request_matches():
    """An idle engine still takes the full-prefill fast path under mixed
    mode; output identity with the classic engine holds trivially."""
    ref = _mk(0).generate(GenRequest("r", PROMPT[:32], max_tokens=6,
                                     temperature=0.0, ignore_eos=True))
    out = _mk(8).generate(GenRequest("r", PROMPT[:32], max_tokens=6,
                                     temperature=0.0, ignore_eos=True))
    assert out == ref


def test_mixed_seeded_sampling_matches_classic():
    """Same fold_in(slot_key, position) PRNG chains ride the mixed program:
    seeded non-greedy sampling is identical too."""
    kw = dict(max_tokens=6, temperature=0.8, top_p=0.9, seed=123,
              ignore_eos=True)
    classic, mixed = [], []
    for dst, m in ((classic, 0), (mixed, 8)):
        eng = _mk(m)
        toks = {}
        eng.add_request(GenRequest("live", [9, 8, 7], **kw))
        for _ in range(3):
            _collect(toks, eng.step())
        eng.add_request(GenRequest("long", PROMPT[:32], **kw))
        while eng.has_work:
            _collect(toks, eng.step())
        dst.append(toks)
    assert mixed == classic


def test_mixed_lora_parity_with_classic():
    """LoRA threads through the mixed program unchanged: adapter + base
    streams decoding while an adapter prompt prefills give the classic
    path's tokens exactly."""
    import jax

    from dynamo_tpu.lora import apply as lora_apply
    from dynamo_tpu.models import llama
    from dynamo_tpu.models.config import ModelConfig

    mcfg = ModelConfig()
    params = llama.init_params(mcfg, jax.random.PRNGKey(0))
    ada = lora_apply.random_adapter(mcfg, rank=4, seed=1, scale=0.3)

    def run(mixed):
        eng = Engine(EngineConfig(
            model="tiny-debug", page_size=4, num_pages=128, max_num_seqs=4,
            max_seq_len=96, enforce_eager=True, prefill_chunk_tokens=8,
            mixed_batch_tokens=mixed, lora_slots=2, lora_rank=4),
            params=dict(params))
        eng.lora.register("ada", tensors=ada, rank=4)
        out = {}
        eng.add_request(GenRequest("base", [1, 2, 3], max_tokens=8,
                                   temperature=0.0, ignore_eos=True))
        eng.add_request(GenRequest("alive", [4, 5, 6], max_tokens=8,
                                   temperature=0.0, ignore_eos=True,
                                   adapter="ada"))
        for _ in range(4):
            _collect(out, eng.step())
        eng.add_request(GenRequest("along", PROMPT[:32], max_tokens=4,
                                   temperature=0.0, ignore_eos=True,
                                   adapter="ada"))
        while eng.has_work:
            _collect(out, eng.step())
        return out, eng

    classic, _ = run(0)
    mixed, eng = run(8)
    assert mixed == classic
    assert eng.metrics.mixed_count >= 1


def test_mixed_preemption_recovery_matches_classic():
    """Page pressure mid-mixed-step: preemption + automatic recovery leave
    greedy outputs identical to the classic path under the same pressure."""
    kw = dict(num_pages=16, max_num_seqs=3, max_seq_len=96)

    def run(mixed):
        eng = _mk(mixed, **kw)
        reqs = [GenRequest(f"s{i}", [(i * 7 + j) % 90 + 1 for j in range(6)],
                           max_tokens=14, temperature=0.0, ignore_eos=True)
                for i in range(2)]
        out = {}
        for r in reqs:
            eng.add_request(r)
        for _ in range(3):
            _collect(out, eng.step())
        eng.add_request(GenRequest("long", PROMPT[:32], max_tokens=4,
                                   temperature=0.0, ignore_eos=True))
        while eng.has_work:
            _collect(out, eng.step())
        return out, eng.metrics.num_preempted

    classic, pre_c = run(0)
    mixed, pre_m = run(8)
    assert mixed == classic
    assert pre_m >= 1, "scenario must actually exercise preemption"


def test_prefix_cache_namespaces_hold_through_mixed_chunks():
    """Satellite: the prefix-caching x chunked-prefill exclusion is lifted
    for the ragged path; cached prefixes re-enter as mid-prompt chunks
    through the mixed step, and adapter namespaces never cross."""
    import jax

    from dynamo_tpu.lora import apply as lora_apply
    from dynamo_tpu.models import llama
    from dynamo_tpu.models.config import ModelConfig

    mcfg = ModelConfig()
    params = llama.init_params(mcfg, jax.random.PRNGKey(0))
    eng = Engine(EngineConfig(
        model="tiny-debug", page_size=4, num_pages=128, max_num_seqs=4,
        max_seq_len=96, enforce_eager=True, enable_prefix_caching=True,
        mixed_batch_tokens=8, lora_slots=2, lora_rank=4),
        params=dict(params))
    assert eng.prefix_cache is not None  # exclusion lifted for mixed mode
    eng.lora.register("ada",
                      tensors=lora_apply.random_adapter(mcfg, rank=4,
                                                        seed=1, scale=0.3),
                      rank=4)
    prompt = PROMPT[:24]

    def gen(rid, adapter):
        # a live stream keeps the batch busy so the prompt's chunks (cached
        # prefix re-entry included) ride the mixed step, not idle prefill
        eng.add_request(GenRequest(f"{rid}-live", [1, 2, 3], max_tokens=6,
                                   temperature=0.0, ignore_eos=True))
        for _ in range(2):
            eng.step()
        eng.add_request(GenRequest(rid, prompt, max_tokens=4,
                                   temperature=0.0, ignore_eos=True,
                                   adapter=adapter))
        toks = []
        while eng.has_work:
            for ev in eng.step():
                if ev.request_id == rid and ev.token_id >= 0:
                    toks.append(ev.token_id)
        return toks

    first = gen("a1", "ada")
    hits_after_insert = eng.prefix_cache.hits
    base = gen("b1", None)  # same tokens, base namespace: must NOT hit
    assert eng.prefix_cache.hits == hits_after_insert, \
        "base request hit an adapter-namespaced prefix"
    assert base  # base run completed (its own namespace, fresh prefill)
    second = gen("a2", "ada")  # same namespace: hits, identical tokens
    assert eng.prefix_cache.hits > hits_after_insert
    assert second == first
    assert eng.metrics.mixed_count >= 1


def test_mixed_abort_mid_prefill_releases_pages():
    eng = _mk(8)
    eng.add_request(GenRequest("live", [1, 2, 3], max_tokens=20,
                               temperature=0.0, ignore_eos=True))
    eng.step()
    free0 = eng.allocator.free_pages
    eng.add_request(GenRequest("long", PROMPT, max_tokens=4,
                               temperature=0.0, ignore_eos=True))
    for _ in range(2):
        eng.step()  # inflight started, at least one mixed step ran
    assert eng._inflight is not None
    eng.abort_request("long")
    evs = eng.step()
    assert any(e.request_id == "long" and e.finish_reason == "abort"
               for e in evs)
    assert eng._inflight is None
    eng.abort_request("live")
    while eng.has_work:
        eng.step()
    assert eng.allocator.free_pages >= free0


# ------------------------------------------------- jitted acceptance bar --


@pytest.mark.slow
def test_mixed_jit_acceptance_matches_classic():
    """Acceptance: the donated jitted mixed program (LoRA in-batch) is
    token-identical to the classic jitted chunk/decode path."""
    import jax

    from dynamo_tpu.lora import apply as lora_apply
    from dynamo_tpu.models import llama
    from dynamo_tpu.models.config import ModelConfig

    mcfg = ModelConfig()
    params = llama.init_params(mcfg, jax.random.PRNGKey(0))
    ada = lora_apply.random_adapter(mcfg, rank=4, seed=1, scale=0.3)

    def run(mixed):
        eng = Engine(EngineConfig(
            model="tiny-debug", page_size=4, num_pages=128, max_num_seqs=4,
            max_seq_len=96, prefill_chunk_tokens=8,
            mixed_batch_tokens=mixed, lora_slots=2, lora_rank=4),
            params=dict(params))
        eng.lora.register("ada", tensors=ada, rank=4)
        out = {}
        eng.add_request(GenRequest("live", [1, 2, 3], max_tokens=12,
                                   temperature=0.0, ignore_eos=True))
        eng.add_request(GenRequest("alive", [4, 5, 6], max_tokens=12,
                                   temperature=0.0, ignore_eos=True,
                                   adapter="ada"))
        for _ in range(4):
            _collect(out, eng.step())
        eng.add_request(GenRequest("long", PROMPT, max_tokens=6,
                                   temperature=0.0, ignore_eos=True))
        while eng.has_work:
            _collect(out, eng.step())
        return out, eng

    classic, _ = run(0)
    mixed, eng = run(8)
    assert mixed == classic
    assert eng.metrics.mixed_count >= 1
    assert "mixed_False" in eng._jit_handles or eng._jit_handles


@pytest.mark.slow
def test_mixed_jit_int8_kv_matches_classic():
    """Acceptance: identity holds with an int8-quantized KV pool riding the
    jitted mixed program."""
    kw = dict(model="tiny-debug", page_size=4, num_pages=128,
              max_num_seqs=3, max_seq_len=96, prefill_chunk_tokens=8,
              kv_cache_dtype="int8")
    classic = _interference(Engine(EngineConfig(mixed_batch_tokens=0, **kw)))
    eng = Engine(EngineConfig(mixed_batch_tokens=8, **kw))
    mixed = _interference(eng)
    assert mixed == classic
    assert eng.metrics.mixed_count >= 1


# ------------------------------------------------------------ bench smoke --


def test_prefill_interference_bench_cpu_smoke(monkeypatch):
    """The A/B scenario runs end-to-end on CPU and honors the result
    contract; CPU numbers are flagged non-comparable (ROADMAP constraint)."""
    import bench

    for k, v in (("BENCH_MIX_STREAMS", "2"), ("BENCH_MIX_PROMPTS", "1"),
                 ("BENCH_MIX_PROMPT_TOKENS", "24"), ("BENCH_MIX_TOKENS", "4"),
                 ("BENCH_MIX_BUDGET", "8")):
        monkeypatch.setenv(k, v)
    res = bench.bench_prefill_interference(on_tpu=False)
    assert res["scenario"] == "prefill_interference"
    assert res["comparable"] is False
    assert res["metric"] == "prefill_interference_itl_p95"
    assert res["value"] > 0
    for arm in ("mixed_on", "mixed_off"):
        for src in ("engine", "measured"):
            assert res[arm][src]["itl_p95_ms"] >= res[arm][src]["itl_p50_ms"]
    assert res["mixed_on"]["mixed_steps"] >= 1
    assert res["mixed_off"]["mixed_steps"] == 0
    assert res["itl_p95_speedup"] > 0
