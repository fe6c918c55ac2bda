"""Falcon-H1's structure at a toy size (`tiny-falcon-h1-debug`: three layers,
each attention AND Mamba-2 on one normed input, then a gated MLP, every
multiplier set) against its float32 reference
(dynamo_tpu/models/reference/falcon_h1.py): the serving path's forward
functions (a whole prompt, a prompt in chunks with the state carried, decode
through the pages and the state slots, mixed steps, fused steps) on logits;
the three forms of the Mamba-2 mixer at 2 groups; the state update's kernel
over a pool of states under a layer's offset; every multiplier seen; what
padding and empty slots may not touch; and the refusals of
`from_hf_config`. Tolerances: tests/falcon_h1_common.py."""

import dataclasses
import filecmp
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dynamo_tpu.engine.kv_cache import KVCacheSpec, alloc_kv_pages
from dynamo_tpu.models import llama
from dynamo_tpu.models.config import PARALLEL, ModelConfig, Multipliers
from dynamo_tpu.models.reference import falcon_h1 as ref
from dynamo_tpu.ops import ssm as ssm_ops

from falcon_h1_common import ATOL, RTOL, hf_dict, tiny

PS = 4       # page size
CHUNK = 8    # prompt chunk: two scan chunks of 4
REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
CUT = os.path.join(REPO, "benchmarks/chip/configs/falcon-h1-34b-w8a8-1chip")
TOKENS = [int(t) for t in np.random.default_rng(0).integers(1, 500, 45)]


def _jitted(fn):
    return jax.jit(fn, static_argnums=(0,), static_argnames=("page_size",))


prefill, prefill_chunk, decode_step, mixed_step = (
    _jitted(f) for f in (llama.prefill, llama.prefill_chunk,
                         llama.decode_step, llama.mixed_step))


@pytest.fixture(scope="module")
def model():
    cfg = tiny()
    p = llama.init_params(cfg, jax.random.PRNGKey(3))
    # a conv bias, a D and norm weights that matter
    p["ssm_conv_b"] = 0.1 * jax.random.normal(
        jax.random.PRNGKey(5), p["ssm_conv_b"].shape, jnp.float32)
    p["ssm_norm"] = 1.0 + 0.3 * jax.random.normal(
        jax.random.PRNGKey(6), p["ssm_norm"].shape, jnp.float32)
    p["ssm_d"] = 1.0 + 0.3 * jax.random.normal(
        jax.random.PRNGKey(7), p["ssm_d"].shape, jnp.float32)
    # the three branches about as loud as one another in the residual, the
    # logits O(1): a multiplier taken as 1 then moves them far past ATOL
    p["wo"] = p["wo"] * 4.0
    p["ssm_out"] = p["ssm_out"] * 4.0
    p["w_down"] = p["w_down"] * 4.0
    p["lm_head"] = p["lm_head"] * 20.0
    return cfg, p


def _reference(cfg, p, tokens, **replace):
    rc = dataclasses.replace(ref.Config.from_hf(hf_dict(cfg)), **replace)
    return ref.forward(rc, ref.dequantize(p), jnp.asarray(tokens))


@pytest.fixture(scope="module")
def want(model):
    return _reference(*model, TOKENS)


def _pools(cfg, slots=2):
    spec = KVCacheSpec.from_model(cfg, num_pages=32, page_size=PS,
                                  state_slots=slots)
    assert spec.num_layers == spec.state_layers == cfg.num_layers == 3
    assert spec.state_stacked
    return alloc_kv_pages(spec)


def _table(n_tokens, first_page=1):
    n = -(-n_tokens // PS)
    # the bucket's pages and a chunk's trash tail (page_table_width)
    return jnp.concatenate([
        jnp.arange(first_page, first_page + n, dtype=jnp.int32),
        jnp.zeros((CHUNK // PS,), jnp.int32)])


def _run_program(cfg, p, tokens, n_chunked=37, slot=0, mixed=False):
    """The serving path's forward functions: the first `n_chunked` tokens
    in 8-token chunks (the last one padded: 37 = 4 x 8 + 5), then decode
    steps in a batch of two slots of which the other is empty. With `mixed`
    the chunks ride llama.mixed_step beside an EMPTY decode batch's rows.
    Returns ({position: logits}, k_pages, v_pages)."""
    kp, vp = _pools(cfg)
    table = _table(len(tokens))
    pages = llama.SlotPages(table, jnp.int32(slot))
    toks = jnp.asarray(tokens + [0] * CHUNK, jnp.int32)
    idle = dict(tokens=jnp.zeros((2,), jnp.int32),
                positions=jnp.zeros((2,), jnp.int32),
                block_tables=jnp.zeros((2, table.shape[0]), jnp.int32),
                context_lens=jnp.ones((2,), jnp.int32))
    got = {}
    for start in range(0, n_chunked, CHUNK):
        n = min(CHUNK, n_chunked - start)
        chunk = jnp.where(jnp.arange(CHUNK) < n, toks[start:start + CHUNK], 7)
        if mixed:
            out = mixed_step(
                cfg, p, idle["tokens"], idle["positions"],
                idle["block_tables"], idle["context_lens"], chunk,
                jnp.int32(start), jnp.int32(n), pages, kp, vp, page_size=PS)
            got[start + n - 1] = out.chunk_logits
        else:
            out = prefill_chunk(cfg, p, chunk, jnp.int32(start), jnp.int32(n),
                                kp, vp, pages, page_size=PS)
            got[start + n - 1] = out.last_logits
        kp, vp = out.k_pages, out.v_pages
    tables = jnp.zeros((2, table.shape[0]), jnp.int32).at[slot].set(table)
    for pos in range(n_chunked, len(tokens)):
        one = lambda v: jnp.zeros((2,), jnp.int32).at[slot].set(v)
        out = decode_step(
            cfg, p, one(tokens[pos]), one(pos), tables,
            jnp.ones((2,), jnp.int32).at[slot].set(pos + 1), kp, vp,
            page_size=PS)
        kp, vp = out.k_pages, out.v_pages
        got[pos] = out.logits[slot]
    return got, kp, vp


@pytest.fixture(scope="module")
def program(model):
    """The chunked prompt then decode, once for the tests that judge it."""
    return _run_program(*model, TOKENS, slot=1)


def _worst(got, want):
    return max(float(np.max(np.abs(np.asarray(v) - np.asarray(want[pos]))))
               for pos, v in got.items())


# -------------------------------------------------- the program's forwards --

def test_chunked_prefill_then_decode_matches_reference(program, want):
    """A prompt fed in chunks (the state handed from chunk to chunk through
    its slot of EVERY layer, the keys through its pages, the last chunk
    padded), then decode through both, against the reference's full
    forward. The program applies the multipliers FOLDED (one vector on
    W_in's output, attention_in and key on q / k / v, lm_head on the normed
    row), the reference each where the published description puts it: that
    the two agree is this test."""
    got, _, _ = program
    assert sorted(got) == [7, 15, 23, 31] + list(range(36, 45))
    assert float(np.max(np.abs(want))) > 0.5  # logits of O(1)
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
    got, _, _ = _run_program(cfg, p, TOKENS, mixed=True)
    for pos, logits in got.items():
        np.testing.assert_allclose(logits, want[pos], rtol=RTOL, atol=ATOL)


def test_a_mixed_step_beside_a_decoder_touches_neither_others_store(model,
                                                                     want):
    """Slot 0 decodes while a second prompt's chunks ride the same mixed
    steps into slot 1: both match the reference on their own tokens."""
    cfg, p = model
    other = [int(t) for t in np.random.default_rng(5).integers(1, 500, 21)]
    want_other = _reference(cfg, p, other)
    _, kp, vp = _run_program(cfg, p, TOKENS[:20], n_chunked=20, slot=0)
    t0, t1 = _table(45, 1), _table(45, 14)
    tables = jnp.stack([t0, jnp.zeros_like(t0)])
    pos = 20
    for start in range(0, 21, CHUNK):
        n = min(CHUNK, 21 - start)
        chunk = jnp.asarray((other[start:start + n] + [3] * CHUNK)[:CHUNK],
                            jnp.int32)
        out = mixed_step(
            cfg, p, jnp.asarray([TOKENS[pos], 0], jnp.int32),
            jnp.asarray([pos, 0], jnp.int32), tables,
            jnp.asarray([pos + 1, 1], jnp.int32), chunk, jnp.int32(start),
            jnp.int32(n), llama.SlotPages(t1, jnp.int32(1)), kp, vp,
            page_size=PS)
        kp, vp = out.k_pages, out.v_pages
        np.testing.assert_allclose(out.logits[0], want[pos], rtol=RTOL,
                                   atol=ATOL)
        np.testing.assert_allclose(out.chunk_logits,
                                   want_other[start + n - 1], rtol=RTOL,
                                   atol=ATOL)
        pos += 1


def test_sixteen_fused_steps_are_sixteen_single_ones(model, want):
    """The decode window carries the pools on the device from step to step
    (a lax.scan over decode_step, itself a scan over the layers): 16 fused
    steps give the logits of 16 single ones and the reference's."""
    cfg, p = model
    _, kp, vp = _run_program(cfg, p, TOKENS[:29], n_chunked=29)
    table = _table(45)
    tables = jnp.stack([table, jnp.zeros_like(table)])
    toks = jnp.asarray(TOKENS, jnp.int32)
    slots = llama.live_state_slots(cfg, tables)

    def one(carry, pos):
        kp, vp = carry
        out = llama.decode_step(
            cfg, p, jnp.stack([toks[pos], 0]), jnp.stack([pos, 0]), tables,
            jnp.stack([pos + 1, 1]), kp, vp, page_size=PS, state_slots=slots)
        return (out.k_pages, out.v_pages), out.logits[0]

    steps = jnp.arange(29, 45, dtype=jnp.int32)
    (fk, fv), fused = jax.jit(
        lambda kp, vp: jax.lax.scan(one, (kp, vp), steps))(kp, vp)
    carry, single, step = (kp, vp), [], jax.jit(one)
    for pos in steps:
        carry, logits = step(carry, pos)
        single.append(logits)
    np.testing.assert_allclose(fused, jnp.stack(single), rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(fused, want[29:45], rtol=RTOL, atol=ATOL)
    for a, b in zip(jax.tree.leaves((fk.state, fv.state)),
                    jax.tree.leaves((carry[0].state, carry[1].state))):
        np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-6)


def test_padding_rows_and_empty_slots_leave_states_and_pages_untouched(model):
    """A chunk with NO real row, whatever its tokens, leaves its slot's S
    and conv rows bit for bit in every layer (and the other slot's), and
    every page but the trash page; a decode step moves the live slot's
    state alone, and no page of the other sequence."""
    cfg, p = model
    _, kp, vp = _run_program(cfg, p, TOKENS[:24], n_chunked=24, slot=0)
    table = _table(45)
    out = prefill_chunk(
        cfg, p, jnp.full((CHUNK,), 11, jnp.int32), jnp.int32(24),
        jnp.int32(0), kp, vp, llama.SlotPages(table, jnp.int32(0)),
        page_size=PS)
    for before, after in zip(jax.tree.leaves((kp.state, vp.state)),
                             jax.tree.leaves((out.k_pages.state,
                                              out.v_pages.state))):
        np.testing.assert_array_equal(before, after)
    # the chunk's rows went to its own pages 7, 8 (positions 24-31): every
    # page before them, the cached context, is as it was
    np.testing.assert_array_equal(kp.pages[:, 1:7], out.k_pages.pages[:, 1:7])
    tables = jnp.stack([table, jnp.zeros_like(table)])
    out = decode_step(
        cfg, p, jnp.asarray([TOKENS[24], 5], jnp.int32),
        jnp.asarray([24, 0], jnp.int32), tables,
        jnp.asarray([25, 1], jnp.int32), kp, vp, page_size=PS)
    for before, after in zip(jax.tree.leaves((kp.state, vp.state)),
                             jax.tree.leaves((out.k_pages.state,
                                              out.v_pages.state))):
        assert before.shape[:2] == (3, 2)  # (layer, slot)
        np.testing.assert_array_equal(before[:, 1], after[:, 1])
        for layer in range(3):
            assert float(jnp.max(jnp.abs(before[layer, 0]
                                         - after[layer, 0]))) > 0
    # the empty slot wrote the trash page alone
    np.testing.assert_array_equal(kp.pages[:, 8:], out.k_pages.pages[:, 8:])


def test_a_first_chunk_starts_from_zero_whatever_the_slot_held(model, want):
    cfg, p = model
    _, kp, vp = _run_program(cfg, p, TOKENS[:24], n_chunked=24, slot=0)
    assert float(jnp.max(jnp.abs(kp.state[0][:, 0]))) > 0
    table = _table(45, first_page=14)
    out = prefill_chunk(
        cfg, p, jnp.asarray(TOKENS[:CHUNK], jnp.int32), jnp.int32(0),
        jnp.int32(CHUNK), kp, vp, llama.SlotPages(table, jnp.int32(0)),
        page_size=PS)
    np.testing.assert_allclose(out.last_logits, want[CHUNK - 1], rtol=RTOL,
                               atol=ATOL)


# ---------------------------------------------------------- the multipliers --

MULTIPLIERS = ([(name, None) for name in ref.SCALARS]
               + [("ssm_multipliers", i) for i in range(5)]
               + [("mlp_multipliers", i) for i in range(2)])


@pytest.mark.parametrize(
    "name,index", MULTIPLIERS,
    ids=[n if i is None else f"{n}[{i}]" for n, i in MULTIPLIERS])
def test_every_multiplier_is_seen(model, program, name, index):
    """The reference with ONE multiplier taken as 1 is another model: the
    program's logits fail the comparison against it, at 50 times the
    tolerance. All fourteen published numbers, each alone."""
    cfg, p = model
    got, _, _ = program
    rc = ref.Config.from_hf(hf_dict(cfg))
    value = getattr(rc, name)
    if index is None:
        assert value != 1.0
        change = {name: 1.0}
    else:
        assert value[index] != 1.0
        change = {name: value[:index] + (1.0,) + value[index + 1:]}
    wrong = _reference(cfg, p, TOKENS, **change)
    assert _worst(got, wrong) > 50 * ATOL, (name, index)


def test_the_multipliers_are_fourteen_distinct_numbers_none_of_them_one():
    m = tiny().multipliers
    flat = [v for v in m[:7]] + list(m.ssm) + list(m.mlp)
    assert len(flat) == len(set(flat)) == 14 and 1.0 not in flat


def test_the_folded_vector_is_the_applied_multipliers(model):
    """W_in is linear: ssm_in on its input and the five ssm_multipliers on
    its output's runs [z | x | B | C | dt] are one vector on the output."""
    cfg, _ = model
    rc = ref.Config.from_hf(hf_dict(cfg))
    np.testing.assert_allclose(
        llama._mup_vector(cfg), rc.ssm_in_multiplier * rc.mup_vector,
        rtol=1e-7)
    assert llama._mup_vector(cfg).shape == (
        2 * cfg.mamba_d_inner + 2 * cfg.mamba_n_groups * cfg.ssm_state_size
        + cfg.mamba_num_heads,)


# ------------------------------------------------------ the Mamba-2 mixer --

def _mixer_inputs(t, h=4, p=8, g=2, n=8, seed=0):
    rng = np.random.default_rng(seed)
    return dict(
        x=jnp.asarray(rng.normal(size=(t, h, p)), jnp.float32),
        dt=jnp.asarray(rng.uniform(1e-3, 0.5, (t, h)), jnp.float32),
        a=-jnp.asarray(rng.uniform(1, 8, (h,)), jnp.float32),
        bm=jnp.asarray(rng.normal(size=(t, g, n)), jnp.float32),
        cm=jnp.asarray(rng.normal(size=(t, g, n)), jnp.float32),
        d=jnp.asarray(rng.normal(size=(h,)), jnp.float32))


def test_the_three_forms_of_the_recurrence_agree_at_two_groups():
    """13 tokens through the chunked scan (chunks of 4, a state handed
    in), through `step` one token at a time, and through the kernel
    `update_live` (interpret mode) one token at a time: the same y and the
    same final state, with 4 heads reading 2 groups' B / C rows."""
    v = _mixer_inputs(13)
    init = jnp.asarray(np.random.default_rng(1).normal(size=(4, 8, 8)),
                       jnp.float32)
    x12 = {k: (a[:12] if k in ("x", "dt", "bm", "cm") else a)
           for k, a in v.items()}
    y_scan, s_scan = ssm_ops.scan_chunked(
        x12["x"], x12["dt"], v["a"], x12["bm"], x12["cm"], v["d"], init, 4)
    live = jnp.asarray([True])
    slots = ssm_ops.live_slots(live)
    s_step = s_kern = init[None]
    for t in range(12):
        row = (v["x"][t][None], v["dt"][t][None], v["a"], v["bm"][t][None],
               v["cm"][t][None], v["d"])
        y_step, s_step = ssm_ops.step(*row, s_step)
        y_kern, s_kern = ssm_ops.update_live(*row, s_kern, live, slots,
                                             interpret=True)
        np.testing.assert_allclose(y_step[0], y_scan[t], rtol=1e-4,
                                   atol=1e-5)
        # the kernel's products are `step`'s; the compiler may fuse a
        # multiply and an add in one of the two programs and not the other
        np.testing.assert_allclose(y_kern, y_step, rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(s_step[0], s_scan, rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(s_kern, s_step, rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize("layer", [0, 2])
def test_update_live_over_a_pool_under_a_layers_offset(layer):
    """The kernel (interpret mode) over a POOL of three layers' states
    [3 x 5 slots, 4 heads, 8, 128] in blocks of 2 heads (two head blocks a
    slot), handed `base` = layer x 5: the layer's live slots come out bit
    for bit as the plain kernel over that layer's rows alone gives them,
    and as `step_every_slot` does within a rounding (the two programs fuse
    a multiply and an add differently on the CPU); its dead slots, and
    every row of the other layers, bit for bit as they were; y of a dead
    row 0. dt and the decay stay indexed by the slot."""
    b, h, p, g, n = 5, 4, 8, 2, 128
    v = _mixer_inputs(b, h, p, g, n, seed=3)
    pool = jnp.asarray(np.random.default_rng(4).normal(
        size=(3 * b, h, p, n)), jnp.float32)
    live = jnp.asarray([True, False, True, True, False])
    own = pool[layer * b:(layer + 1) * b]
    want_y, want_s = ssm_ops.step_every_slot(
        v["x"], v["dt"], v["a"], v["bm"], v["cm"], v["d"], own, live)
    y, new = ssm_ops.update_live(
        v["x"], v["dt"], v["a"], v["bm"], v["cm"], v["d"], pool, live,
        ssm_ops.live_slots(live), base=jnp.int32(layer * b), interpret=True,
        head_block=2)
    plain_y, plain_s = ssm_ops.update_live(
        v["x"], v["dt"], v["a"], v["bm"], v["cm"], v["d"], own, live,
        ssm_ops.live_slots(live), interpret=True, head_block=2)
    mine = new[layer * b:(layer + 1) * b]
    np.testing.assert_array_equal(mine, plain_s)
    np.testing.assert_array_equal(y, plain_y)
    np.testing.assert_array_equal(mine[~live], own[~live])
    np.testing.assert_array_equal(y[~live], 0.0)
    np.testing.assert_allclose(mine, want_s, rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(y[live], want_y[live], rtol=1e-5, atol=1e-5)
    others = np.ones((3 * b,), bool)
    others[layer * b:(layer + 1) * b] = False
    np.testing.assert_array_equal(new[others], pool[others])


def test_update_takes_the_kernel_or_its_twin_under_a_base():
    """`ops/ssm.update` with a `base`: the XLA twin slices the layer's rows
    out of the pool and puts them back; the interpret-mode kernel writes
    them where they lie. Same states."""
    from dynamo_tpu.ops import attention as att

    b, h, p, g, n = 3, 4, 8, 2, 128
    v = _mixer_inputs(b, h, p, g, n, seed=5)
    pool = jnp.asarray(np.random.default_rng(6).normal(
        size=(2 * b, h, p, n)), jnp.float32)
    live = jnp.asarray([False, True, True])
    args = (v["x"], v["dt"], v["a"], v["bm"], v["cm"], v["d"], pool, live,
            ssm_ops.live_slots(live))
    outs = {}
    for backend in ("xla", "pallas_interpret"):
        with att.attention_context(backend, None, 1):
            outs[backend] = ssm_ops.update(*args, base=jnp.int32(b))
    np.testing.assert_allclose(outs["xla"][1], outs["pallas_interpret"][1],
                               rtol=1e-5, atol=1e-6)
    for got in outs.values():  # the other layer's rows, and the dead slot
        np.testing.assert_array_equal(got[1][:b], pool[:b])
        np.testing.assert_array_equal(got[1][b], pool[b])


# ------------------------------------------------------------- the config --

def _row():
    with open(CATALOG) as f:
        for line in f:
            row = json.loads(line)
            if row["name"] == "Falcon-H1-34B-Instruct":
                return row
    pytest.skip("the catalog has no Falcon-H1-34B-Instruct row")


def test_from_hf_config_loads_the_published_row_and_the_cut():
    row = _row()
    m = ModelConfig.from_hf_config(row["config"])
    assert m.num_layers == 72 and m.mixer_types == (PARALLEL,) * 72
    assert m.paged_layers == m.state_layers == 72 and m.parallel_mixers
    assert (m.mamba_num_heads, m.mamba_head_dim, m.mamba_n_groups,
            m.ssm_state_size, m.conv_kernel, m.ssm_chunk_size) == (
                32, 128, 2, 256, 4, 128)
    assert (m.mamba_d_inner, m.mamba_conv_dim) == (4096, 5120)
    assert (m.num_heads, m.num_kv_heads, m.head_dim) == (20, 4, 128)
    assert (m.hidden_size, m.intermediate_size, m.vocab_size) == (
        5120, 21504, 261120)
    assert m.rope_theta == 1e11 and not m.tie_word_embeddings
    assert not m.is_moe and m.rope_yarn_scaling is None
    mult = m.multipliers
    assert (mult.embedding, mult.lm_head, mult.attention_in,
            mult.attention_out, mult.ssm_in) == (
                5.656854249492381, 0.0078125, 1, 0.0375, 0.25)
    assert mult.key == 0.011048543456039804
    assert mult.ssm_out == 0.08838834764831845
    assert mult.ssm == (0.3535533905932738, 0.25, 0.1767766952966369, 0.5,
                        0.3535533905932738)
    assert mult.mlp == (0.1767766952966369, 0.011160714285714284)
    with open(os.path.join(CUT, "config.json")) as f:
        cut = json.load(f)
    c = ModelConfig.from_hf_config(cut)
    assert c.num_layers == 10 and c.mixer_types == (PARALLEL,) * 10
    assert cut["layer_types"] == list(c.mixer_types)
    # nothing but the depth differs from the row
    changed = {k for k in row["config"] if cut.get(k) != row["config"][k]}
    assert changed == {"num_hidden_layers"}
    assert dataclasses.replace(c, num_layers=72, mixer_types=m.mixer_types
                               ) == m
    spec = KVCacheSpec.from_model(c, 6144, 16, state_slots=64)
    assert spec.num_layers == spec.state_layers == 10
    assert spec.bytes_per_token() == 20_480  # TEN layers own pages
    assert spec.bytes_per_slot() == 10 * (4_194_304 + 30_720) == 42_250_240
    shapes = {k: v[0] for k, v in llama.param_specs(c).items()}
    assert shapes["ssm_in"] == (10, 5120, 9248)
    assert shapes["ssm_out"] == (10, 4096, 5120)
    assert shapes["w_gate"] == (10, 5120, 21504)
    assert shapes["wq"] == (10, 5120, 20, 128)
    assert shapes["wk"] == (10, 5120, 4, 128)
    assert round(sum(int(np.prod(s)) for s in shapes.values()) / 1e6) == 6975


@pytest.mark.parametrize("change,word", [
    (dict(mamba_rms_norm=False), "mamba_rms_norm=false"),
    (dict(mamba_norm_before_gate=True), "mamba_norm_before_gate=true"),
    (dict(mamba_use_mlp=False), "mamba_use_mlp=false"),
    (dict(mamba_conv_bias=False), "mamba_conv_bias=false"),
    (dict(attention_bias=True), "attention_bias=true"),
    (dict(projectors_bias=True), "projectors_bias=true"),
    (dict(mamba_proj_bias=True), "mamba_proj_bias=true"),
    (dict(mlp_bias=True), "mlp_bias=true"),
    (dict(attn_layer_indices=[0, 2]), "attn_layer_indices"),
    (dict(rope_scaling={"rope_type": "linear", "factor": 2.0}),
     "rope_scaling"),
    (dict(hidden_act="gelu"), "hidden_act"),
    (dict(layer_types=["mamba"] * 3), "layer_types"),
    (dict(mamba_n_heads=6, mamba_d_ssm=48, mamba_n_groups=4),
     "multiple of mamba_n_groups"),
    (dict(mamba_d_ssm=128), "mamba_d_ssm"),
    (dict(ssm_multipliers=[1.0, 2.0]), "five entries"),
], ids=["rms_norm", "norm_before_gate", "use_mlp", "conv_bias",
        "attention_bias", "projectors_bias", "proj_bias", "mlp_bias",
        "attn_layer_indices", "rope_scaling", "hidden_act", "layer_types",
        "heads_groups", "d_ssm", "ssm_multipliers"])
def test_from_hf_config_refuses_by_name_what_is_not_served(change, word):
    cfg = dict(hf_dict(tiny()), **change)
    with pytest.raises(ValueError, match=word):
        ModelConfig.from_hf_config(cfg)


def test_the_tiny_preset_is_what_from_hf_config_makes_of_its_spelling():
    cfg = tiny()
    got = ModelConfig.from_hf_config(hf_dict(cfg), name=cfg.name,
                                     dtype="float32")
    assert got == cfg


@pytest.mark.parametrize("change,word", [
    (dict(mixer_types=(PARALLEL, "mamba", PARALLEL)), "no layer of another"),
    (dict(multipliers=None), "multipliers"),
    (dict(num_experts=4), "without experts"),
    (dict(tie_word_embeddings=True), "three forms"),
    (dict(mamba_n_groups=3), "multiple of"),
], ids=["mixed_kinds", "no_multipliers", "experts", "tied_head", "groups"])
def test_a_model_config_refuses_what_the_block_is_not(change, word):
    with pytest.raises(ValueError, match=word):
        tiny(**change)


def test_other_models_refuse_the_multipliers_and_a_hybrid_a_prompt_batch():
    with pytest.raises(ValueError, match="mixer_types"):
        ModelConfig(multipliers=Multipliers())
    with pytest.raises(NotImplementedError, match="every layer"):
        llama.prefill_batch(tiny(), {}, jnp.zeros((1, 4), jnp.int32), None,
                            None, None, None, page_size=PS)


def test_the_benchmark_keeps_a_copy_of_the_reference():
    assert filecmp.cmp(
        os.path.join(REPO, "dynamo_tpu/models/reference/falcon_h1.py"),
        os.path.join(REPO, "benchmarks/chip/reference/falcon_h1.py"),
        shallow=False)
