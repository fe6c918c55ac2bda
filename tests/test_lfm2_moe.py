"""LFM2-MoE's structure at a toy size (`tiny-lfm2-moe-debug`: nine layers
`c c a c c c a c c`, each an operator and then an FFN) against its float32
reference (dynamo_tpu/models/reference/lfm2_moe.py): the serving path's
forward functions (a whole prompt, a prompt in chunks with the two conv rows
carried, decode through the pages and the state slots, mixed steps, fused
steps) on logits; the two forms of the gated short convolution; every
mechanism seen; the four shares of an expert layer summing to the whole; the
attention kernels at 64-lane heads, 4 a KV head; what padding and empty
slots may not touch; the seeded loader; and the refusals of `from_hf_config`.
Tolerances: tests/lfm2_moe_common.py."""

import dataclasses
import filecmp
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dynamo_tpu.engine.kv_cache import KVCacheSpec, alloc_kv_pages
from dynamo_tpu.models import llama, loader, quant
from dynamo_tpu.models.config import ATTENTION, CONV, PRESETS, ModelConfig
from dynamo_tpu.models.reference import lfm2_moe as ref
from dynamo_tpu.ops import attention as att
from dynamo_tpu.ops import pallas_attention as pa
from dynamo_tpu.ops import short_conv

from lfm2_moe_common import ATOL, RTOL, drawn, hf_dict, tiny

PS = 4       # page size
CHUNK = 8    # prompt chunk
REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
PUBLISHED = os.path.join(REPO,
                         "benchmarks/chip/configs/lfm2-8b-a1b-w8a8-1chip")
TOKENS = [int(t) for t in np.random.default_rng(0).integers(1, 500, 45)]


def _jitted(fn):
    return jax.jit(fn, static_argnums=(0,), static_argnames=("page_size",))


prefill, prefill_chunk, decode_step, mixed_step = (
    _jitted(f) for f in (llama.prefill, llama.prefill_chunk,
                         llama.decode_step, llama.mixed_step))


@pytest.fixture(scope="module")
def model():
    cfg = tiny()
    return cfg, drawn(cfg)


def _reference(cfg, p, tokens, **kw):
    return ref.forward(ref.Config.from_hf(hf_dict(cfg)), ref.dequantize(p),
                       jnp.asarray(tokens), **kw)


@pytest.fixture(scope="module")
def want(model):
    return _reference(*model, TOKENS)


def _pools(cfg, slots=2):
    spec = KVCacheSpec.from_model(cfg, num_pages=32, page_size=PS,
                                  state_slots=slots)
    assert (spec.num_layers, spec.state_layers) == (2, 7)
    assert spec.state_stacked and spec.ssm_shape == ()
    return alloc_kv_pages(spec)


def _table(n_tokens, first_page=1):
    n = -(-n_tokens // PS)
    # the bucket's pages and a chunk's trash tail (page_table_width)
    return jnp.concatenate([
        jnp.arange(first_page, first_page + n, dtype=jnp.int32),
        jnp.zeros((CHUNK // PS,), jnp.int32)])


def _run_program(cfg, p, tokens, cuts, slot=0, mixed=False, pools=None):
    """The serving path's forward functions: the prompt's first cuts[-1]
    tokens in chunks that END at each of `cuts` (8-row programs, the rows
    past a chunk's real ones padding; a chunk starts at a page boundary, as
    the engine's do), then decode steps in a batch of two slots of which
    the other is empty. With `mixed` the chunks ride llama.mixed_step beside
    an EMPTY decode batch's rows. Returns ({position: logits}, k_pages,
    v_pages)."""
    kp, vp = pools or _pools(cfg)
    table = _table(len(tokens))
    pages = llama.SlotPages(table, jnp.int32(slot))
    toks = jnp.asarray(tokens + [0] * CHUNK, jnp.int32)
    idle = (jnp.zeros((2,), jnp.int32), jnp.zeros((2,), jnp.int32),
            jnp.zeros((2, table.shape[0]), jnp.int32),
            jnp.ones((2,), jnp.int32))
    got, start = {}, 0
    for end in cuts:
        n = end - start
        assert start % PS == 0 and 0 < n <= CHUNK
        chunk = jnp.where(jnp.arange(CHUNK) < n, toks[start:start + CHUNK], 7)
        if mixed:
            out = mixed_step(cfg, p, *idle, chunk, jnp.int32(start),
                             jnp.int32(n), pages, kp, vp, page_size=PS)
            got[end - 1] = out.chunk_logits
        else:
            out = prefill_chunk(cfg, p, chunk, jnp.int32(start), jnp.int32(n),
                                kp, vp, pages, page_size=PS)
            got[end - 1] = out.last_logits
        kp, vp, start = out.k_pages, out.v_pages, end
    tables = jnp.zeros((2, table.shape[0]), jnp.int32).at[slot].set(table)
    for pos in range(cuts[-1], len(tokens)):
        one = lambda v: jnp.zeros((2,), jnp.int32).at[slot].set(v)  # noqa
        out = decode_step(
            cfg, p, one(tokens[pos]), one(pos), tables,
            jnp.ones((2,), jnp.int32).at[slot].set(pos + 1), kp, vp,
            page_size=PS)
        kp, vp = out.k_pages, out.v_pages
        got[pos] = out.logits[slot]
    return got, kp, vp


CUTS = (8, 16, 24, 32, 37)  # the last chunk 5 real rows and 3 of padding


@pytest.fixture(scope="module")
def program(model):
    """The chunked prompt then decode, once for the tests that judge it."""
    return _run_program(*model, TOKENS, CUTS, slot=1)


# -------------------------------------------------- the program's forwards --

def test_chunked_prefill_then_decode_matches_reference(program, want):
    """A prompt fed in chunks (the two conv rows handed from chunk to chunk
    through its slot of every conv layer, the keys through its pages, the
    last chunk padded), then decode through both, against the reference's
    full forward."""
    got, _, _ = program
    assert sorted(got) == [7, 15, 23, 31] + list(range(36, 45))
    assert float(np.max(np.abs(want))) > 0.5  # logits of O(1)
    for pos, logits in got.items():
        np.testing.assert_allclose(logits, want[pos], rtol=RTOL, atol=ATOL)


@pytest.mark.parametrize("cuts", [
    (8, 9), (8, 10), (8, 11), (1,), (2,), (4, 5), (4, 6), (8, 16, 17)],
    ids=["1_row_chunk", "2_row_chunk", "3_row_chunk", "prompt_of_1",
         "prompt_of_2", "1_row_second", "2_rows_second", "1_row_third"])
def test_chunks_of_one_and_two_real_rows_match_reference(model, want, cuts):
    """Chunks of 1 and of 2 real rows (the three taps then reach into the
    slot's rows for both, or for one, of their older inputs), as a prompt's
    first chunk (from zeros) and behind longer ones (a chunk starts on a
    page boundary, so a short one is a prompt's last), every one ending on
    padding rows that may not move the state; then three decode steps
    through the slot. Every split of the taps: the ops' own test below."""
    cfg, p = model
    n = cuts[-1] + 3
    got, _, _ = _run_program(cfg, p, TOKENS[:n], cuts)
    assert set(range(cuts[-1], n)) <= set(got)
    for pos, logits in got.items():
        np.testing.assert_allclose(logits, want[pos], rtol=RTOL, atol=ATOL)


def test_whole_prompt_prefill_matches_reference(model, want):
    """One whole-prompt prefill (bucket 32 for 20 tokens: twelve padding
    rows that may not move the state), then a decode step through the
    slot and the pages."""
    cfg, p = model
    kp, vp = _pools(cfg)
    table = _table(45)
    padded = jnp.asarray(TOKENS[:20] + [9] * 12, jnp.int32)
    out = prefill(cfg, p, padded, jnp.int32(20), kp, vp,
                  llama.SlotPages(table[:8], jnp.int32(1)), page_size=PS)
    np.testing.assert_allclose(out.last_logits, want[19], rtol=RTOL,
                               atol=ATOL)
    tables = jnp.stack([jnp.zeros_like(table), table])
    out = decode_step(
        cfg, p, jnp.asarray([0, TOKENS[20]], jnp.int32),
        jnp.asarray([0, 20], jnp.int32), tables,
        jnp.asarray([1, 21], jnp.int32), out.k_pages, out.v_pages,
        page_size=PS)
    np.testing.assert_allclose(out.logits[1], want[20], rtol=RTOL, atol=ATOL)


def test_chunks_in_mixed_steps_match_reference(model, want):
    cfg, p = model
    got, _, _ = _run_program(cfg, p, TOKENS, CUTS, mixed=True)
    for pos, logits in got.items():
        np.testing.assert_allclose(logits, want[pos], rtol=RTOL, atol=ATOL)


def test_a_mixed_step_beside_a_decoder_touches_neither_others_store(model,
                                                                     want):
    """Slot 0 decodes while a second prompt's chunks ride the same mixed
    steps into slot 1: both match the reference on their own tokens."""
    cfg, p = model
    other = [int(t) for t in np.random.default_rng(5).integers(1, 500, 21)]
    want_other = _reference(cfg, p, other)
    _, kp, vp = _run_program(cfg, p, TOKENS[:20], (8, 16, 20), slot=0)
    t0, t1 = _table(45, 1), _table(45, 14)
    tables = jnp.stack([t0, jnp.zeros_like(t0)])
    pos = 20
    for start in (0, 8, 16):
        n = min(CHUNK, 21 - start)
        chunk = jnp.asarray((other + [0] * CHUNK)[start:start + CHUNK],
                            jnp.int32)
        out = mixed_step(
            cfg, p, jnp.asarray([TOKENS[pos], 0], jnp.int32),
            jnp.asarray([pos, 0], jnp.int32), tables,
            jnp.asarray([pos + 1, 1], jnp.int32), chunk, jnp.int32(start),
            jnp.int32(n), llama.SlotPages(t1, jnp.int32(1)), kp, vp,
            page_size=PS)
        kp, vp = out.k_pages, out.v_pages
        np.testing.assert_allclose(out.logits[0], want[pos],
                                   rtol=RTOL, atol=ATOL)
        np.testing.assert_allclose(out.chunk_logits, want_other[start + n - 1],
                                   rtol=RTOL, atol=ATOL)
        pos += 1


def test_sixteen_fused_steps_are_sixteen_single_ones(model, want):
    """The fused window's form: decode_step inside a lax.scan with the pools
    and the conv rows in the carry, teacher forced, against the reference
    and against single steps."""
    cfg, p = model
    _, kp, vp = _run_program(cfg, p, TOKENS[:24], (8, 16, 24), slot=1)
    table = _table(45)
    tables = jnp.stack([jnp.zeros_like(table), table])
    feed = jnp.asarray(TOKENS[24:40], jnp.int32)

    @jax.jit
    def window(kp, vp):
        slots = llama.live_state_slots(cfg, tables)

        def body(carry, tok):
            pos, kp, vp = carry
            out = llama.decode_step(
                cfg, p, jnp.asarray([0, 0], jnp.int32).at[1].set(tok),
                jnp.asarray([0, 0], jnp.int32).at[1].set(pos), tables,
                jnp.asarray([1, 1], jnp.int32).at[1].set(pos + 1), kp, vp,
                page_size=PS, state_slots=slots)
            return (pos + 1, out.k_pages, out.v_pages), out.logits[1]
        return jax.lax.scan(body, (jnp.int32(24), kp, vp), feed)

    (_, kf, vf), logits = window(kp, vp)
    for i in range(16):
        np.testing.assert_allclose(logits[i], want[24 + i], rtol=RTOL,
                                   atol=ATOL)
    got, ks, vs = _run_program(cfg, p, TOKENS[:40], (8, 16, 24), slot=1)
    np.testing.assert_allclose(vf.state[0], vs.state[0], rtol=RTOL, atol=ATOL)
    np.testing.assert_allclose(logits[15], got[39], rtol=RTOL, atol=ATOL)


def test_padding_rows_and_empty_slots_leave_states_and_pages_untouched(model):
    """A chunk's padding rows leave the slot's rows as the last REAL row
    left them, whatever the padding holds; an empty slot's rows and every
    other sequence's pages come back bit for bit from chunks and decode
    steps alike."""
    cfg, p = model
    kp, vp = _pools(cfg)
    marked = vp.state[0].at[:, 0].set(3.25)  # what slot 0's last tenant left
    vp = llama.StatePools(vp.pages, (marked,))
    table = _table(45)
    pages = llama.SlotPages(table, jnp.int32(1))
    outs = []
    for pad in (7, 401):
        chunk = jnp.asarray(TOKENS[:5] + [pad] * 3, jnp.int32)
        outs.append(prefill_chunk(cfg, p, chunk, jnp.int32(0), jnp.int32(5),
                                  kp, vp, pages, page_size=PS))
    a, b = outs
    assert np.array_equal(a.v_pages.state[0], b.v_pages.state[0])
    np.testing.assert_array_equal(a.last_logits, b.last_logits)
    assert np.all(np.asarray(a.v_pages.state[0][:, 0]) == 3.25)
    assert np.any(np.asarray(a.v_pages.state[0][:, 1]) != 0)
    tables = jnp.stack([jnp.zeros_like(table), table])
    out = decode_step(
        cfg, p, jnp.asarray([77, TOKENS[5]], jnp.int32),
        jnp.asarray([0, 5], jnp.int32), tables,
        jnp.asarray([1, 6], jnp.int32), a.k_pages, a.v_pages, page_size=PS)
    assert np.all(np.asarray(out.v_pages.state[0][:, 0]) == 3.25)
    assert not np.array_equal(out.v_pages.state[0][:, 1],
                              a.v_pages.state[0][:, 1])
    # pages nobody owns (11 on) hold what they held
    assert not np.any(np.asarray(out.k_pages.pages[:, 13:]))


def test_a_first_chunk_starts_from_zero_whatever_the_slot_held(model, want):
    cfg, p = model
    kp, vp = _pools(cfg)
    vp = llama.StatePools(vp.pages, (vp.state[0] + 5.0,))
    got, _, _ = _run_program(cfg, p, TOKENS[:12], (8, 9), slot=1,
                             pools=(kp, vp))
    for pos, logits in got.items():
        np.testing.assert_allclose(logits, want[pos], rtol=RTOL, atol=ATOL)


# ------------------------------------------------------ the operator's ops --

def test_the_two_forms_of_the_gated_conv_agree_and_are_the_equations():
    """gated_rows over a sequence, the same in two chunks through the kept
    rows, and gated_step a token at a time (one live slot of two) against
    y_t = C_t * sum_k w_k g_{t-2+k}, g = B * u."""
    rng = np.random.default_rng(1)
    t, e, k = 11, 8, 3
    bcu = jnp.asarray(rng.standard_normal((t, 3 * e)), jnp.float32)
    w = jnp.asarray(rng.standard_normal((k, e)), jnp.float32)
    b, c, u = (np.asarray(bcu[:, i * e:(i + 1) * e]) for i in range(3))
    g = np.concatenate([np.zeros((2, e), np.float32), b * u])
    want = c * sum(np.asarray(w)[j] * g[j:j + t] for j in range(k))
    zeros = jnp.zeros((2, e), jnp.float32)
    whole, kept = short_conv.gated_rows(bcu, zeros, w, t)
    np.testing.assert_allclose(whole, want, rtol=1e-6, atol=1e-6)
    np.testing.assert_array_equal(kept, g[-2:])
    # every split of the sequence: s real rows and two of padding, then the
    # rest from the kept rows (s = 1, 2: the taps reach the zeros before the
    # sequence through the kept rows)
    for s in range(1, t):
        pad = jnp.concatenate([bcu[:s], 9.0 + bcu[:2]])
        first, kept = short_conv.gated_rows(pad, zeros, w, s)
        np.testing.assert_array_equal(kept, g[s:s + 2])
        rest, _ = short_conv.gated_rows(bcu[s:], kept, w, t - s)
        np.testing.assert_allclose(jnp.concatenate([first[:s], rest]), want,
                                   rtol=1e-6, atol=1e-6)
    prev = jnp.stack([jnp.full((2, e), 7.0), jnp.zeros((2, e))])
    live = jnp.asarray([False, True])
    for i in range(t):
        y, prev = short_conv.gated_step(
            jnp.stack([bcu[i] + 1.0, bcu[i]]), prev, w, live)
        np.testing.assert_allclose(y[1], want[i], rtol=1e-6, atol=1e-6)
        assert np.all(np.asarray(prev[0]) == 7.0)  # the dead slot's rows
    np.testing.assert_array_equal(prev[1], g[-2:])


MECHANISMS = {
    "no_oldest_tap": "a tap", "no_b_gate": "the B gate",
    "no_c_gate": "the C gate", "swap_bc": "which run gates where",
    "no_qk_norm": "the q / k norms", "no_rope": "the rotary",
    "no_select_bias": "the selection bias"}


@pytest.mark.parametrize("variant", sorted(MECHANISMS) + [
    "state_zeroed_at_chunks", "dense_width_halved", "untied_head"])
def test_each_mechanism_is_seen(model, program, want, variant):
    """Dropping a tap, either gate, the q / k norms, the rotary, the
    selection bias, the state at a chunk boundary, half the dense layers'
    width or the tied head moves the reference's logits at the program's
    positions by at least 50 x the tolerance: the comparison above would
    fail for a program that left it out."""
    cfg, p = model
    got, _, _ = program
    if variant in MECHANISMS:
        other = _reference(cfg, p, TOKENS, variant=variant)
    elif variant == "state_zeroed_at_chunks":
        other = _reference(cfg, p, TOKENS, zero_state_every=CHUNK)
    elif variant == "dense_width_halved":
        half = cfg.dense_intermediate_size // 2
        other = _reference(cfg, dict(p, **{
            "dense.w_down": p["dense.w_down"].at[:, half:].set(0.0)}), TOKENS)
    else:  # the PROGRAM with a head of its own in place of the embedding's
        untied = tiny(tie_word_embeddings=False)
        head = 0.2 * jax.random.normal(
            jax.random.PRNGKey(9), (cfg.hidden_size, cfg.vocab_size))
        other, _, _ = _run_program(untied, dict(p, lm_head=head), TOKENS,
                                   CUTS, slot=1)
    worst = max(float(np.max(np.abs(np.asarray(other[pos]) - np.asarray(
        want[pos])))) for pos in got)
    assert worst > 50 * ATOL, (variant, worst)
    for pos, logits in got.items():  # and the program sides with the model
        np.testing.assert_allclose(logits, want[pos], rtol=RTOL, atol=ATOL)


def test_the_selection_bias_moves_picks_and_never_weights(model):
    cfg, p = model
    rc = ref.Config.from_hf(hf_dict(cfg))
    lp = ref.layer_params(rc, ref.dequantize(p), 4)
    x = jax.random.normal(jax.random.PRNGKey(2), (40, cfg.hidden_size))
    picked, w = ref.route(rc, lp, x)
    plain, w_plain = ref.route(rc, lp, x, "no_select_bias")
    assert np.mean(np.sort(picked, -1) != np.sort(plain, -1)) > 0.1
    np.testing.assert_allclose(np.sum(w, -1), 1.0, atol=1e-4)
    same = np.all(np.sort(picked, -1) == np.sort(plain, -1), axis=-1)
    assert same.any()
    np.testing.assert_allclose(np.sort(w[same], -1), np.sort(w_plain[same], -1),
                               rtol=1e-6)


def test_the_four_shares_expert_layers_sum_to_the_uncut_reference(model):
    """`tiny-lfm2-moe-ep4-debug` holds experts 4-7 of 16: the expert layer
    of each of the four shares (the router whole, the held experts' part
    alone) summed is the uncut reference's layer, and a share's whole
    forward is the reference's forward over that share."""
    cfg, p = model
    rc = ref.Config.from_hf(hf_dict(cfg))
    fp = ref.dequantize(p)
    layer = 3  # an expert layer behind a conv operator
    lp_ref = ref.layer_params(rc, fp, layer)
    x = jax.random.normal(jax.random.PRNGKey(4), (24, cfg.hidden_size))
    with jax.default_matmul_precision("highest"):
        whole = ref.experts(rc, lp_ref, x)
    total = jnp.zeros_like(whole)
    for first in (0, 4, 8, 12):
        share = tiny("tiny-lfm2-moe-ep4-debug", local_expert_offset=first)
        assert share.held_experts == 4 and share.moe_grouped
        lp = {"router": p["router"][layer - 2],
              "router_bias": p["router_bias"][layer - 2],
              **{k: p[k][:, first:first + 4] for k in llama._EXPERT_STACKS},
              "moe_layer": jnp.int32(layer - 2)}
        y, counts = llama._mlp(share, lp, x)
        with jax.default_matmul_precision("highest"):
            part = ref.experts(rc, {**lp_ref, **{
                k: lp_ref[k][first:first + 4]
                for k in llama._EXPERT_STACKS}}, x, first, 4)
        np.testing.assert_allclose(y, part, rtol=RTOL, atol=ATOL)
        assert int(counts[0]) == 24 * 2 and 0 < int(counts[1]) < 48
        total = total + y
    np.testing.assert_allclose(total, whole, rtol=RTOL, atol=ATOL)
    # a share through the whole program: its experts' part of every layer
    share = tiny("tiny-lfm2-moe-ep4-debug")
    ps = dict(p, **{k: p[k][:, 4:8] for k in llama._EXPERT_STACKS})
    assert {k: v.shape for k, v in ps.items()} == {
        k: tuple(s[0]) for k, s in llama.param_specs(share).items()}
    got, _, _ = _run_program(share, ps, TOKENS[:20], (8, 16, 17))
    want_share = _reference(share, ps, TOKENS[:20], first=4, count=4)
    for pos, logits in got.items():
        np.testing.assert_allclose(logits, want_share[pos], rtol=RTOL,
                                   atol=ATOL)


def test_the_program_holds_a_bounded_number_of_layer_bodies(model):
    """Two scans whatever the depth: the lowered decode step of nine layers
    and of a model of twenty-one (the same kinds, three times the expert
    layers) hold the same number of while loops, conditionals and expert
    layers' sorts."""
    cfg, _ = model
    deep = tiny(num_layers=21, mixer_types=cfg.mixer_types[:2] + (
        cfg.mixer_types[2:] * 3)[:19])

    def lowered(c):
        kp, vp = alloc_kv_pages(KVCacheSpec.from_model(c, 32, PS,
                                                       state_slots=2))
        shapes = jax.eval_shape(lambda: llama.init_params(
            c, jax.random.PRNGKey(0)))
        t = _table(45)
        return jax.jit(decode_step.__wrapped__, static_argnums=(0,),
                       static_argnames=("page_size",)).lower(
            c, shapes, jnp.zeros((2,), jnp.int32), jnp.zeros((2,), jnp.int32),
            jnp.stack([t, t]), jnp.ones((2,), jnp.int32), kp, vp,
            page_size=PS).as_text()

    a, b = lowered(cfg), lowered(deep)
    for word in ("stablehlo.while", "stablehlo.case", "stablehlo.sort"):
        assert a.count(word) == b.count(word) > 0, word


# ------------------------------------- attention at 64 lanes a head, 4 : 1 --

def _kv64(rng, pages, ps, n_kv, d):
    return tuple(jnp.asarray(rng.standard_normal((pages, ps, n_kv * d)),
                             jnp.float32) for _ in range(2))


@pytest.mark.parametrize("kernel", ["decode", "ragged", "chunk", "prefill"])
def test_attention_kernels_at_64_lane_heads_are_their_xla_twins(kernel):
    """The four paged kernels (interpret mode) at the published head shape:
    64 lanes a head, 4 query heads a KV head (2 KV heads here: a 128-lane
    row passes the lane gate as the published 512 does), against the XLA
    compositions; an empty decode slot reads nothing and gives zeros."""
    rng = np.random.default_rng(len(kernel))
    n_kv, group, d, ps = 2, 4, 64, 16
    h = n_kv * group
    kp, vp = _kv64(rng, 96, ps, n_kv, d)
    before = dict(att.pallas_fallback_counts())
    kw = dict(page_size=ps, num_kv_heads=n_kv)
    if kernel == "decode":
        q = jnp.asarray(rng.standard_normal((5, h, d)), jnp.float32)
        tables = jnp.asarray(rng.permutation(np.arange(1, 61))[:50].reshape(
            5, 10), jnp.int32)
        ctx = jnp.asarray([1, 37, 40, 41, 150], jnp.int32)
        with att.attention_context("xla", None, 1):
            want = att.paged_attention_decode(q, kp, vp, tables, ctx, **kw)
        with att.attention_context("pallas_interpret", None, 1):
            got = att.paged_attention_decode(q, kp, vp, tables, ctx, **kw,
                                             kernel_lens=ctx.at[0].set(0))
        assert not np.any(np.asarray(got[0]))
        got, want = got[1:], want[1:]
    elif kernel in ("ragged", "chunk"):
        decode = 3 if kernel == "ragged" else 0
        q = jnp.asarray(rng.standard_normal((decode + 32, h, d)), jnp.float32)
        tables = jnp.asarray(rng.permutation(np.arange(1, 46)).reshape(
            3, 15)[:decode], jnp.int32)
        ctx = jnp.asarray([5, 41, 200][:decode], jnp.int32)
        pages = jnp.asarray(rng.permutation(np.arange(46, 96))[:20],
                            jnp.int32)
        outs = {}
        for backend in ("xla", "pallas_interpret"):
            with att.attention_context(backend, None, 1):
                outs[backend] = (att.ragged_mixed_attention(
                    q, kp, vp, tables, ctx, pages, 13 * ps,
                    num_decode=decode, **kw) if decode else
                    att.chunk_attention(q, kp, vp, pages, 13 * ps, **kw))
        got, want = outs["pallas_interpret"], outs["xla"]
    else:
        q = jnp.asarray(rng.standard_normal((48, h, d)), jnp.float32)
        k, v = (jnp.asarray(rng.standard_normal((48, n_kv, d)), jnp.float32)
                for _ in range(2))
        want = att.prefill_attention_xla(q, k, v, 41)[:41]
        got = pa.prefill_attention(q, k, v, 41, interpret=True)[:41]
    assert got.shape[1:] == (h, d)
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)
    assert dict(att.pallas_fallback_counts()) == before  # none was demoted


# --------------------------------------------------- parameters and config --

def test_the_seeded_loader_draws_the_new_leaves():
    """loader.random_quantized_params over the operator-then-FFN tree: the
    three projections of every operator, the dense FFNs, the experts and
    the embedding int8 with scales, the conv's taps and the norms in the
    model's dtype, the selection bias float32 and zero; no lm_head (tied)."""
    cfg = PRESETS["tiny-lfm2-moe-debug"]
    p = loader.random_quantized_params(cfg, seed=3, mode="w8a8")
    specs = llama.param_specs(cfg)
    assert set(p) == set(specs) and "lm_head" not in p
    for name in ("conv_in", "conv_out", "wq", "wk", "wv", "wo", "embed",
                 "dense.w_gate", "dense.w_up", "dense.w_down", "moe_w_gate",
                 "moe_w_up", "moe_w_down"):
        assert isinstance(p[name], quant.QTensorA8), name
        assert p[name].q.shape == specs[name][0] and p[name].q.dtype == np.int8
    assert p["conv_w"].shape == (7, 3, 64) and p["conv_w"].dtype == jnp.bfloat16
    assert np.std(np.asarray(p["conv_w"], np.float32)) > 0.3
    assert p["router_bias"].dtype == np.float32 and not p["router_bias"].any()
    for name in ("operator_norm", "ffn_norm", "q_norm", "k_norm"):
        assert np.all(np.asarray(p[name], np.float32) == 1.0)
    assert quant.quant_axes("dense.w_down") == quant.quant_axes("w_down")


def test_from_hf_config_loads_the_published_row():
    """The catalog row's config and the benchmark's config.json (the row
    plus the two ASSUMED keys) map to one ModelConfig: nothing is cut."""
    with open(CATALOG) as f:
        row = next(r for r in map(json.loads, f) if r["name"] == "LFM2-8B-A1B")
    with open(os.path.join(PUBLISHED, "config.json")) as f:
        held = json.load(f)
    assert {k: held[k] for k in row["config"]} == row["config"]
    assert set(held) - set(row["config"]) == {"architectures",
                                              "tie_word_embeddings"}
    cfg = ModelConfig.from_hf_config(held, name="x")
    assert cfg == ModelConfig.from_hf_config(row["config"], name="x")
    assert (cfg.num_layers, cfg.hidden_size, cfg.head_dim) == (24, 2048, 64)
    assert (cfg.num_heads, cfg.num_kv_heads) == (32, 8)
    assert cfg.mixer_types.count(CONV) == 18 == cfg.state_layers
    assert [i for i, k in enumerate(cfg.mixer_types) if k == ATTENTION] == [
        2, 6, 10, 14, 18, 21]
    assert (cfg.conv_kernel, cfg.first_k_dense) == (3, 2)
    assert (cfg.dense_intermediate_size, cfg.intermediate_size) == (7168, 1792)
    assert (cfg.num_experts, cfg.num_experts_per_tok) == (32, 4)
    assert cfg.moe_scoring == "sigmoid" and cfg.router_bias
    assert cfg.norm_topk_prob and cfg.routed_scaling_factor == 1.0
    assert cfg.qk_norm and cfg.tie_word_embeddings and cfg.moe_grouped
    assert (cfg.rope_theta, cfg.rms_norm_eps) == (1e6, 1e-5)
    assert cfg.vocab_size == 65536 and cfg.num_moe_layers == 22
    specs = llama.param_specs(cfg)
    assert specs["conv_in"][0] == (18, 2048, 6144)
    assert specs["conv_w"][0] == (18, 3, 2048)
    assert specs["q_norm"][0] == (6, 64) and "lm_head" not in specs
    assert specs["dense.w_gate"][0] == (2, 2048, 7168)
    assert specs["moe_w_gate"][0] == (22, 32, 2048,
                                      cfg.expert_dims_stored[1])


def test_the_tiny_preset_is_what_from_hf_config_makes_of_its_spelling():
    cfg = PRESETS["tiny-lfm2-moe-debug"]
    assert ModelConfig.from_hf_config(hf_dict(cfg), name=cfg.name) == cfg


@pytest.mark.parametrize("change,word", [
    (dict(conv_bias=True), "conv_bias"),
    (dict(conv_L_cache=1), "conv_L_cache"),
    (dict(num_dense_layers=9), "num_dense_layers"),
    (dict(use_expert_bias=False), "use_expert_bias"),
    (dict(norm_topk_prob=False), "norm_topk_prob"),
    (dict(layer_types=["conv"] * 8 + ["sliding_attention"]), "layer_types"),
    (dict(layer_types=["conv"] * 8), "layer_types"),
    (dict(rope_scaling={"rope_type": "yarn", "factor": 4.0}), "rope_scaling"),
    (dict(rope_parameters={"rope_type": "linear", "factor": 2.0}),
     "rope_parameters"),
    (dict(num_experts_per_tok=17), "num_experts_per_tok"),
    (dict(sliding_window=128), "sliding_window"),
    (dict(model_type="lfm2"), "model_type"),
], ids=lambda v: v if isinstance(v, str) else "")
def test_from_hf_config_refuses_by_name_what_it_cannot_serve(change, word):
    spelled = dict(hf_dict(PRESETS["tiny-lfm2-moe-debug"]), **change)
    with pytest.raises(ValueError, match=word):
        ModelConfig.from_hf_config(spelled, name="x")


@pytest.mark.parametrize("change,word", [
    (dict(mixer_types=("conv", "mamba") + ("conv",) * 7), "operator"),
    (dict(conv_kernel=1), "conv_kernel"),
    (dict(qk_norm=False), "q/k"),
    (dict(moe_scoring="softmax"), "sigmoid"),
    (dict(num_shared_experts=1), "shared"),
    (dict(sliding_window=8), "three forms"),
    (dict(attention_bias=True), "three forms"),
], ids=["another_kind", "one_tap", "no_qk_norm", "softmax", "shared_expert",
        "window", "bias"])
def test_a_model_config_refuses_what_the_block_is_not(change, word):
    with pytest.raises(ValueError, match=word):
        tiny(**change)


def test_other_hybrids_keep_their_refusals_and_a_prompt_batch_is_refused():
    with pytest.raises(ValueError, match="three forms"):
        dataclasses.replace(PRESETS["tiny-nemotron-h-debug"], qk_norm=True)
    with pytest.raises(ValueError, match="three forms"):
        dataclasses.replace(PRESETS["tiny-nemotron-h-debug"],
                            tie_word_embeddings=True)
    with pytest.raises(NotImplementedError, match="hybrid"):
        llama.prefill_batch(tiny(), {}, None, None, None, None, None,
                            page_size=PS)


def test_the_benchmark_keeps_a_copy_of_the_reference():
    assert filecmp.cmp(
        os.path.join(REPO, "dynamo_tpu/models/reference/lfm2_moe.py"),
        os.path.join(REPO, "benchmarks/chip/reference/lfm2_moe.py"),
        shallow=False)


def test_the_cost_file_counts_a_state_once_a_call_and_seven_operations():
    import importlib.util
    spec = importlib.util.spec_from_file_location(
        "short_conv_cost", os.path.join(
            REPO, "benchmarks/chip/kernel_costs/short_conv.py"))
    cost = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(cost)
    grew = {"metrics.conv.decode_rows": 10.0,
            "metrics.conv.chunk_tokens": 90.0,
            "metrics.conv.chunk_calls": 2.0}.get
    got = cost.from_counters(lambda path: grew(path, 0.0),
                             {"layers": 18, "hidden_size": 2048})
    # four row-moves a token row; the two state rows in and out once a
    # sequence a call: ten decode rows' and two chunks'
    assert got["bytes"] == 18 * 2048 * 2 * (4 * 100 + 4 * 12)
    assert got["ops"] == 100 * 18 * 2048 * 7


def test_a_checkpoint_is_refused_by_name_until_one_has_been_read(tmp_path):
    with pytest.raises(NotImplementedError, match="lfm2_moe"):
        loader.load_hf_safetensors(tiny(), [str(tmp_path / "x.safetensors")])
