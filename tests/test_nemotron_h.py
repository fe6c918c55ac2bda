"""Nemotron-H's structure at a toy size (`tiny-nemotron-h-debug`: the nine
letters MEMEM*EME, every layer ONE mixer) against its float32 reference
(dynamo_tpu/models/reference/nemotron_h.py): the chunked scan against the
token-by-token recurrence, the reference's mixer against `transformers`'
Mamba-2 in torch, the serving path's forward functions (prompt in chunks,
decode through the state slots, mixed steps, fused steps) on logits, what
padding and empty slots may not touch, the six wrong variants, and the
refusals of `from_hf_config`. Tolerances: tests/nemotron_h_common.py."""

import filecmp
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dynamo_tpu.engine.kv_cache import KVCacheSpec, alloc_kv_pages
from dynamo_tpu.models import llama
from dynamo_tpu.models.config import ATTENTION, EXPERTS, MAMBA, ModelConfig
from dynamo_tpu.models.reference import nemotron_h as ref
from dynamo_tpu.ops import ssm as ssm_ops

from nemotron_h_common import ATOL, RTOL, hf_dict, tiny

PS = 4       # page size
CHUNK = 8    # prompt chunk: two scan chunks of 4
REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
CUT = os.path.join(REPO, "benchmarks/chip/configs/nemotron3-nano-w8a8-1chip")
TOKENS = [int(t) for t in np.random.default_rng(0).integers(1, 500, 45)]


def _jitted(fn):
    """The model's entry point compiled once a shape (the config and the
    page size static): nine unrolled layers run eagerly cost seconds a
    call, and these tests make some sixty."""
    return jax.jit(fn, static_argnums=(0,), static_argnames=("page_size",))


prefill, prefill_chunk, decode_step, mixed_step = (
    _jitted(f) for f in (llama.prefill, llama.prefill_chunk,
                         llama.decode_step, llama.mixed_step))


@pytest.fixture(scope="module")
def model():
    cfg = tiny()
    p = llama.init_params(cfg, jax.random.PRNGKey(3))
    # a selection bias that moves picks, a conv bias and a D that matter
    p["router_bias"] = 0.1 * jax.random.normal(
        jax.random.PRNGKey(4), p["router_bias"].shape, jnp.float32)
    p["ssm_conv_b"] = 0.1 * jax.random.normal(
        jax.random.PRNGKey(5), p["ssm_conv_b"].shape, jnp.float32)
    p["ssm_norm"] = 1.0 + 0.3 * jax.random.normal(
        jax.random.PRNGKey(6), p["ssm_norm"].shape, jnp.float32)
    # the state-space branch as loud in the residual as the others
    p["ssm_out"] = p["ssm_out"] * 4.0
    return cfg, p


@pytest.fixture(scope="module")
def want(model):
    cfg, p = model
    return ref.forward(ref.Config.from_hf(hf_dict(cfg)), ref.dequantize(p),
                       jnp.asarray(TOKENS))


def _pools(cfg, slots=2):
    spec = KVCacheSpec.from_model(cfg, num_pages=32, page_size=PS,
                                  state_slots=slots)
    assert spec.num_layers == cfg.mixer_layers(ATTENTION) == 1
    assert spec.state_layers == cfg.mixer_layers(MAMBA) == 4
    return alloc_kv_pages(spec)


def _table(n_tokens, first_page=1):
    n = -(-n_tokens // PS)
    # the bucket's pages and a chunk's trash tail (page_table_width)
    return jnp.concatenate([
        jnp.arange(first_page, first_page + n, dtype=jnp.int32),
        jnp.zeros((CHUNK // PS,), jnp.int32)])


def _run_program(cfg, p, tokens, n_chunked=37, slot=0, pad_as_real=False,
                 mixed=False):
    """The serving path's forward functions: the first `n_chunked` tokens
    in 8-token chunks (the last one padded: 37 = 4 x 8 + 5), then decode
    steps in a batch of two slots of which the other is empty. With
    `mixed` the chunks ride llama.mixed_step beside an EMPTY decode batch's
    rows. Returns ({position: logits}, k_pages, v_pages)."""
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
        n_arg = jnp.int32(CHUNK if pad_as_real else n)
        if mixed:
            out = mixed_step(
                cfg, p, idle["tokens"], idle["positions"],
                idle["block_tables"], idle["context_lens"], chunk,
                jnp.int32(start), n_arg, pages, kp, vp, page_size=PS)
            got[start + n - 1] = out.chunk_logits
        else:
            out = prefill_chunk(cfg, p, chunk, jnp.int32(start), n_arg,
                                      kp, vp, pages, page_size=PS)
            got[start + n - 1] = out.last_logits
        kp, vp = out.k_pages, out.v_pages
    if pad_as_real:
        got.clear()  # the chunks' own last rows are not what is judged
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


def _worst(got, want):
    return max(float(np.max(np.abs(np.asarray(v) - np.asarray(want[pos]))))
               for pos, v in got.items())


# ---------------------------------------------------------------- the scan --

def _recurrence(x, dt, a, bm, cm, d, init):
    """S_t = exp(dt_t a) S_{t-1} + dt_t x_t (x) B_t, y_t = S_t C_t + D x_t,
    one token at a time in float64."""
    x, dt, a, bm, cm, d = (np.asarray(v, np.float64)
                           for v in (x, dt, a, bm, cm, d))
    s = np.asarray(init, np.float64).copy()
    h = x.shape[1]
    rep = h // bm.shape[1]
    ys = []
    for t in range(x.shape[0]):
        b_t, c_t = np.repeat(bm[t], rep, 0), np.repeat(cm[t], rep, 0)
        s = (np.exp(dt[t] * a)[:, None, None] * s
             + (dt[t][:, None] * x[t])[:, :, None] * b_t[:, None, :])
        ys.append(np.einsum("hpn,hn->hp", s, c_t) + d[:, None] * x[t])
    return np.stack(ys), s


@pytest.mark.parametrize("length", [3, 4, 13, 22])
@pytest.mark.parametrize("with_init", [False, True], ids=["zero", "init"])
def test_chunked_scan_equals_the_recurrence(length, with_init):
    """Lengths that are no multiple of the chunk (4) are padded with dt = 0
    rows, as the model pads a prompt: the real rows' outputs and the final
    state are the recurrence's over the real rows alone."""
    h, p, g, n, chunk = 4, 8, 2, 8, 4
    ks = jax.random.split(jax.random.PRNGKey(length), 7)
    padded = -(-length // chunk) * chunk
    x = jax.random.normal(ks[0], (padded, h, p))
    dt = jax.nn.softplus(jax.random.normal(ks[1], (padded, h)) - 1.0)
    dt = jnp.where(jnp.arange(padded)[:, None] < length, dt, 0.0)
    a = -jnp.exp(jax.random.uniform(ks[2], (h,)) * 2.0)
    bm = jax.random.normal(ks[3], (padded, g, n))
    cm = jax.random.normal(ks[4], (padded, g, n))
    d = jax.random.normal(ks[5], (h,))
    init = (jax.random.normal(ks[6], (h, p, n)) if with_init
            else jnp.zeros((h, p, n)))
    y, final = ssm_ops.scan_chunked(x, dt, a, bm, cm, d, init, chunk)
    want_y, want_s = _recurrence(x[:length], dt[:length], a, bm[:length],
                                 cm[:length], d, init)
    np.testing.assert_allclose(y[:length], want_y, rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(final, want_s, rtol=1e-4, atol=1e-4)


def test_one_token_update_is_the_recurrence_and_skips_empty_slots():
    h, p, g, n = 4, 8, 2, 8
    ks = jax.random.split(jax.random.PRNGKey(1), 6)
    x = jax.random.normal(ks[0], (3, h, p))
    dt = jax.nn.softplus(jax.random.normal(ks[1], (3, h)))
    dt = dt.at[1].set(0.0)  # slot 1 is empty
    a = -jnp.exp(jax.random.uniform(ks[2], (h,)))
    bm = jax.random.normal(ks[3], (3, g, n))
    cm = jax.random.normal(ks[4], (3, g, n))
    state = jax.random.normal(ks[5], (3, h, p, n))
    y, new = ssm_ops.step(x, dt, a, bm, cm, jnp.ones((h,)), state)
    np.testing.assert_array_equal(new[1], state[1])  # bit for bit
    for b in (0, 2):
        want_y, want_s = _recurrence(x[b:b + 1], dt[b:b + 1], a, bm[b:b + 1],
                                     cm[b:b + 1], np.ones(h), state[b])
        np.testing.assert_allclose(new[b], want_s, rtol=1e-5, atol=1e-5)
        np.testing.assert_allclose(y[b], want_y[0], rtol=1e-5, atol=1e-5)


def test_conv_keeps_the_last_real_rows():
    """conv_rows hands out the K-1 rows before the NEXT real token, however
    many padding rows follow; fed in pieces it is the conv over the whole."""
    k, c = 4, 6
    ks = jax.random.split(jax.random.PRNGKey(2), 3)
    rows = jax.random.normal(ks[0], (11, c))
    w, b = jax.random.normal(ks[1], (k, c)), jax.random.normal(ks[2], (c,))
    whole, _ = ssm_ops.conv_rows(rows, jnp.zeros((k - 1, c)), w, b, 11)
    prev, out = jnp.zeros((k - 1, c)), []
    for start, n in ((0, 8), (8, 3)):  # the second piece: 3 real of 8
        piece = jnp.concatenate([rows[start:start + n],
                                 jnp.full((8 - n, c), 9.0)])
        o, prev = ssm_ops.conv_rows(piece, prev, w, b, n)
        out.append(o[:n])
    np.testing.assert_allclose(jnp.concatenate(out), whole, rtol=1e-6,
                               atol=1e-6)
    np.testing.assert_array_equal(prev, rows[8:11])
    # a piece with no real row leaves the kept rows as they were
    _, same = ssm_ops.conv_rows(jnp.full((8, c), 9.0), prev, w, b, 0)
    np.testing.assert_array_equal(same, prev)
    # one token a slot: a live slot shifts, an empty one keeps its rows
    step_out, kept = ssm_ops.conv_step(
        jnp.stack([rows[3], rows[3]]), jnp.stack([rows[:3], rows[:3]]), w, b,
        jnp.asarray([True, False]))
    np.testing.assert_allclose(step_out[0], whole[3], rtol=1e-6, atol=1e-6)
    np.testing.assert_array_equal(kept[0], rows[1:4])
    np.testing.assert_array_equal(kept[1], rows[:3])


# ------------------------------------------- the reference against torch --

def test_reference_mixer_is_transformers_mamba2(model):
    """The reference's Mamba-2 mixer on copied weights against
    `transformers`' Mamba2Mixer.torch_forward (plain torch on the CPU): it
    settles the order of W_in's output [z | x B C | dt], the conv, the
    softplus, the groups of B and C, D, and gate-before-norm. Mamba2Mixer
    norms over ALL lanes, which is the reference's "one_norm" control; the
    norm by group is held to Zamba2RMSNormGated beside it."""
    torch = pytest.importorskip("torch")
    from transformers.models.mamba2.configuration_mamba2 import Mamba2Config
    from transformers.models.mamba2.modeling_mamba2 import Mamba2Mixer
    from transformers.models.zamba2.modeling_zamba2 import Zamba2RMSNormGated

    cfg, p = model
    rc = ref.Config.from_hf(hf_dict(cfg))
    lp = ref.layer_params(rc, ref.dequantize(p), 2)  # the second M layer
    d_in = cfg.mamba_d_inner
    tc = Mamba2Config(
        num_heads=cfg.mamba_num_heads, head_dim=cfg.mamba_head_dim,
        hidden_size=cfg.hidden_size, state_size=cfg.ssm_state_size,
        expand=d_in / cfg.hidden_size, conv_kernel=cfg.conv_kernel,
        n_groups=cfg.mamba_n_groups, use_bias=False, use_conv_bias=True,
        layer_norm_epsilon=cfg.rms_norm_eps, chunk_size=4, num_hidden_layers=1)
    mixer = Mamba2Mixer(tc, layer_idx=0).float()
    t = lambda a: torch.tensor(np.asarray(a, np.float32))
    with torch.no_grad():
        mixer.in_proj.weight.copy_(t(lp["ssm_in"]).T)
        mixer.out_proj.weight.copy_(t(lp["ssm_out"]).T)
        mixer.conv1d.weight.copy_(t(lp["ssm_conv_w"]).T[:, None, :])
        mixer.conv1d.bias.copy_(t(lp["ssm_conv_b"]))
        mixer.dt_bias.copy_(t(lp["ssm_dt_bias"]))
        mixer.A_log.copy_(t(lp["ssm_a_log"]))
        mixer.D.copy_(t(lp["ssm_d"]))
        mixer.norm.weight.copy_(t(lp["ssm_norm"]))
        u = jax.random.normal(jax.random.PRNGKey(9), (13, cfg.hidden_size))
        theirs = mixer.torch_forward(t(u)[None])[0].numpy()
    with jax.default_matmul_precision("highest"):
        ours = ref.mamba(rc, lp, u, variant="one_norm")
    np.testing.assert_allclose(ours, theirs, rtol=2e-4, atol=2e-5)

    y = jax.random.normal(jax.random.PRNGKey(10), (5, d_in))
    z = jax.random.normal(jax.random.PRNGKey(11), (5, d_in))
    norm = Zamba2RMSNormGated(d_in, d_in // cfg.mamba_n_groups,
                              eps=cfg.rms_norm_eps)
    with torch.no_grad():
        norm.weight.copy_(t(lp["ssm_norm"]))
        theirs = norm(t(y), t(z)).numpy()
    np.testing.assert_allclose(ref.gate_norm(rc, y, z, lp["ssm_norm"]),
                               theirs, rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(
        ssm_ops.gate_norm(y, z, lp["ssm_norm"], cfg.mamba_n_groups,
                          cfg.rms_norm_eps), theirs, rtol=1e-5, atol=1e-6)


# ------------------------------------------------- the program's forwards --

def test_chunked_prefill_then_decode_matches_reference(model, want):
    """A prompt fed in chunks (the state handed from chunk to chunk through
    its slot, the last chunk padded), then decode through the slot, against
    the reference's full forward; and the five wrong models each fail the
    same tolerance."""
    cfg, p = model
    got, _, _ = _run_program(cfg, p, TOKENS, slot=1)
    assert sorted(got) == [7, 15, 23, 31] + list(range(36, 45))
    for pos, logits in got.items():
        np.testing.assert_allclose(logits, want[pos], rtol=RTOL, atol=ATOL)
    rc, rp = ref.Config.from_hf(hf_dict(cfg)), ref.dequantize(p)
    for variant in ref.VARIANTS[1:]:
        wrong = ref.forward(rc, rp, jnp.asarray(TOKENS), variant=variant)
        assert _worst(got, wrong) > 50 * ATOL, variant


def test_a_state_carried_over_padding_fails(model, want):
    """The sixth wrong variant is the program's own: the last chunk's three
    padding rows taken for real tokens move S and the conv rows, and every
    decoded position after it fails the tolerance."""
    cfg, p = model
    got, _, _ = _run_program(cfg, p, TOKENS, pad_as_real=True)
    assert sorted(got) == list(range(37, 45))
    assert _worst(got, want) > 50 * ATOL


def test_chunks_in_mixed_steps_match_reference(model, want):
    cfg, p = model
    got, _, _ = _run_program(cfg, p, TOKENS, mixed=True)
    for pos, logits in got.items():
        np.testing.assert_allclose(logits, want[pos], rtol=RTOL, atol=ATOL)


def test_whole_prompt_prefill_then_a_mixed_step_beside_a_decoder(model, want):
    """Slot 0: the first 20 tokens as ONE whole-prompt prefill (bucket 32:
    twelve padding rows), then it decodes while a second prompt's chunks
    ride the same mixed steps into slot 1. Neither touches the other's
    slot: both match the reference on their own tokens."""
    cfg, p = model
    rc, rp = ref.Config.from_hf(hf_dict(cfg)), ref.dequantize(p)
    other = [int(t) for t in np.random.default_rng(5).integers(1, 500, 21)]
    want_other = ref.forward(rc, rp, jnp.asarray(other))
    kp, vp = _pools(cfg)
    t0, t1 = _table(45, 1), _table(45, 14)
    padded = jnp.asarray(TOKENS[:20] + [9] * 12, jnp.int32)
    out = prefill(cfg, p, padded, jnp.int32(20), kp, vp,
                        llama.SlotPages(t0[:8], jnp.int32(0)), page_size=PS)
    np.testing.assert_allclose(out.last_logits, want[19], rtol=RTOL,
                               atol=ATOL)
    kp, vp = out.k_pages, out.v_pages
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
    # both decode now, at different lengths, in one batch
    tables = jnp.stack([t0, t1])
    nxt = int(jnp.argmax(out.chunk_logits))
    both = ref.forward(rc, rp, jnp.asarray(other + [nxt]))
    out = decode_step(
        cfg, p, jnp.asarray([TOKENS[pos], nxt], jnp.int32),
        jnp.asarray([pos, 21], jnp.int32), tables,
        jnp.asarray([pos + 1, 22], jnp.int32), kp, vp, page_size=PS)
    np.testing.assert_allclose(out.logits[0], want[pos], rtol=RTOL, atol=ATOL)
    np.testing.assert_allclose(out.logits[1], both[21], rtol=RTOL, atol=ATOL)


def test_sixteen_fused_steps_are_sixteen_single_ones(model, want):
    """The decode window carries the state arrays on the device from step
    to step (a lax.scan over decode_step, as engine.make_decode_window
    runs it): 16 fused steps give the logits of 16 single ones and the
    reference's."""
    cfg, p = model
    _, kp, vp = _run_program(cfg, p, TOKENS[:29], n_chunked=29)
    table = _table(45)
    tables = jnp.stack([table, jnp.zeros_like(table)])
    toks = jnp.asarray(TOKENS, jnp.int32)

    def one(carry, pos):
        kp, vp = carry
        out = llama.decode_step(
            cfg, p, jnp.stack([toks[pos], 0]), jnp.stack([pos, 0]), tables,
            jnp.stack([pos + 1, 1]), kp, vp, page_size=PS)
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


def test_padding_rows_and_empty_slots_leave_a_state_untouched(model):
    """A chunk with NO real row, whatever its tokens, leaves its slot's S
    and conv rows bit for bit (and the other slot's); a decode step moves
    the live slot's state alone."""
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
    tables = jnp.stack([table, jnp.zeros_like(table)])
    out = decode_step(
        cfg, p, jnp.asarray([TOKENS[24], 5], jnp.int32),
        jnp.asarray([24, 0], jnp.int32), tables,
        jnp.asarray([25, 1], jnp.int32), kp, vp, page_size=PS)
    for before, after in zip(jax.tree.leaves((kp.state, vp.state)),
                             jax.tree.leaves((out.k_pages.state,
                                              out.v_pages.state))):
        np.testing.assert_array_equal(before[1], after[1])  # the empty slot
        assert float(jnp.max(jnp.abs(before[0] - after[0]))) > 0


def test_a_first_chunk_starts_from_zero_whatever_the_slot_held(model, want):
    """A reused slot: a prompt's first chunk (start 0) begins from a zero
    state and zero conv rows though the slot still holds its last tenant's."""
    cfg, p = model
    _, kp, vp = _run_program(cfg, p, TOKENS[:24], n_chunked=24, slot=0)
    assert float(jnp.max(jnp.abs(kp.state[0][0]))) > 0
    table = _table(45, first_page=14)
    out = prefill_chunk(
        cfg, p, jnp.asarray(TOKENS[:CHUNK], jnp.int32), jnp.int32(0),
        jnp.int32(CHUNK), kp, vp, llama.SlotPages(table, jnp.int32(0)),
        page_size=PS)
    np.testing.assert_allclose(out.last_logits, want[CHUNK - 1], rtol=RTOL,
                               atol=ATOL)


def test_expert_slices_add_up_to_the_layer(model):
    """The reference's experts a few at a time (what the comparison on the
    chip does at the published widths) sum to the whole layer's output,
    the shared expert counted once."""
    cfg, p = model
    rc = ref.Config.from_hf(hf_dict(cfg))
    lp = ref.layer_params(rc, ref.dequantize(p), 1)
    x = jax.random.normal(jax.random.PRNGKey(7), (6, cfg.hidden_size))
    with jax.default_matmul_precision("highest"):
        whole = ref.experts(rc, lp, x)
        parts = sum(
            ref.experts(rc, dict(lp, moe_w_up=lp["moe_w_up"][f:f + 4],
                                 moe_w_down=lp["moe_w_down"][f:f + 4]),
                        x, first=f, count=4, with_shared=f == 0)
            for f in range(0, cfg.num_experts, 4))
    np.testing.assert_allclose(parts, whole, rtol=1e-5, atol=1e-6)


def test_an_expert_stored_larger_than_the_model_computes_the_model():
    """A hybrid model stores an expert's matrices with zero rows and lanes
    around the model's own (expert_dims_stored: the grouped matmul's tiling
    follows its operands' extents). At a size where both extents are
    padded (hidden 136 -> 256, width 1100 -> 2048) the padding is zero in
    init_params and in the loader's random int8 tree, and the expert layer
    computes what the unpadded matrices compute, grouped and dense."""
    from dynamo_tpu.models.loader import random_quantized_params
    from dynamo_tpu.ops import moe as moe_ops

    cfg = tiny(hidden_size=136, intermediate_size=1100)
    assert cfg.expert_dims_stored == (256, 2048)
    p = llama.init_params(cfg, jax.random.PRNGKey(1))
    q = random_quantized_params(cfg, seed=2, mode="w8a8")
    for tree in (p, q):
        up, down = (tree[k].q if hasattr(tree[k], "q") else tree[k]
                    for k in ("moe_w_up", "moe_w_down"))
        assert up.shape == (4, 16, 256, 2048)
        assert not np.asarray(up[:, :, 136:]).any()
        assert not np.asarray(up[..., 1100:]).any()
        assert not np.asarray(down[:, :, 1100:]).any()
        assert not np.asarray(down[..., 136:]).any()
        assert np.asarray(up[:, :, :136, :1100]).any()
    x = jax.random.normal(jax.random.PRNGKey(3), (10, 136))
    topi = jax.random.randint(jax.random.PRNGKey(4), (10, 2), 0, 16)
    w = jnp.full((10, 2), 0.5)
    up, down = p["moe_w_up"][1], p["moe_w_down"][1]
    cut_up, cut_down = up[:, :136, :1100], down[:, :1100, :136]
    want, _ = moe_ops.moe_mlp_grouped(x, topi, w, None, cut_up, cut_down,
                                      act="relu2")
    got, _ = moe_ops.moe_mlp_grouped(x, topi, w, None, up, down,
                                     act="relu2")
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)
    combine = moe_ops.scatter_combine(topi, w, 16, x.dtype)
    np.testing.assert_allclose(
        moe_ops.moe_mlp_dense(x, combine, None, up, down, act="relu2"),
        want, rtol=1e-4, atol=1e-4)


def test_random_int8_weights_draw_the_step_as_the_family_does():
    """The loader's random-int8 path and init_params draw A_log and dt_bias
    as Mamba-2 initialises them (A in [1, 16), a step in [1e-3, 1e-1]), not
    at sigma 1: exp(dt a) stays near 1 over a long prompt."""
    from dynamo_tpu.models.loader import random_quantized_params
    from dynamo_tpu.models import quant

    cfg = tiny()
    for p in (random_quantized_params(cfg, seed=1, mode="w8a8"),
              llama.init_params(cfg, jax.random.PRNGKey(0))):
        a = np.exp(np.asarray(p["ssm_a_log"], np.float64))
        dt = np.log1p(np.exp(np.asarray(p["ssm_dt_bias"], np.float64)))
        assert a.min() >= 1.0 and a.max() < 16.0
        assert dt.min() >= 1e-3 * 0.999 and dt.max() <= 1e-1 * 1.001
    q = random_quantized_params(cfg, seed=1, mode="w8a8")
    up = np.asarray(q["moe_w_up"].q)
    # no two experts of a layer are the same matrix
    assert len({up[0, e].tobytes() for e in range(up.shape[1])}) == 16
    assert isinstance(q["ssm_in"], quant.QTensorA8)
    assert isinstance(q["moe_w_up"], quant.QTensorA8)
    assert "moe_w_gate" not in q and "w_gate" not in q  # two matrices


# ------------------------------------------------------------ from_hf_config --

def _row():
    with open(CATALOG) as f:
        for line in f:
            row = json.loads(line)
            if row["name"] == "NVIDIA-Nemotron-3-Nano-30B-A3B-BF16":
                return row
    pytest.skip("the catalog has no Nemotron-3-Nano row")


def test_from_hf_config_loads_the_published_row_and_the_cut():
    row = _row()
    m = ModelConfig.from_hf_config(row["config"])
    assert m.num_layers == 52 and len(m.mixer_types) == 52
    assert [m.mixer_layers(k) for k in (MAMBA, EXPERTS, ATTENTION)] == [
        23, 23, 6]
    assert (m.mamba_num_heads, m.mamba_head_dim, m.mamba_n_groups,
            m.ssm_state_size, m.conv_kernel, m.ssm_chunk_size) == (
                64, 64, 8, 128, 4, 128)
    assert (m.mamba_d_inner, m.mamba_conv_dim) == (4096, 6144)
    assert (m.num_heads, m.num_kv_heads, m.head_dim) == (32, 2, 128)
    assert (m.num_experts, m.num_experts_per_tok, m.intermediate_size,
            m.shared_expert_width, m.expert_act) == (128, 6, 1856, 3712,
                                                     "relu2")
    assert m.moe_scoring == "sigmoid" and m.router_bias and m.moe_grouped
    assert m.routed_scaling_factor == 2.5 and not m.tie_word_embeddings
    with open(os.path.join(CUT, "config.json")) as f:
        cut = json.load(f)
    c = ModelConfig.from_hf_config(cut)
    assert c.mixer_types == m.mixer_types[:9] and c.num_layers == 9
    assert cut["layer_types"] == list(c.mixer_types)
    # nothing but the depth differs from the row
    changed = {k for k in row["config"] if cut.get(k) != row["config"][k]}
    assert changed == {"num_hidden_layers", "hybrid_override_pattern"}
    spec = KVCacheSpec.from_model(c, 8192, 16, state_slots=64)
    assert spec.bytes_per_token() == 1024  # ONE attention layer owns pages
    assert spec.bytes_per_slot() == 4 * (2_097_152 + 36_864)
    shapes = {k: v[0] for k, v in llama.param_specs(c).items()}
    assert shapes["ssm_in"] == (4, 2688, 10304)
    # an expert's matrices are STORED 3,072 x 2,048 around the model's
    # 2,688 x 1,856 (ModelConfig.expert_dims_stored); the model's own
    # parameters are ISSUE 42's 6,073 M
    assert shapes["moe_w_up"] == (4, 128, 3072, 2048)
    assert shapes["moe_w_down"] == (4, 128, 2048, 3072)
    stored = sum(int(np.prod(s)) for s in shapes.values())
    padding = 4 * 128 * 2 * (3072 * 2048 - 2688 * 1856)
    assert round((stored - padding) / 1e6) == 6073
    assert round(stored / 1e6) == 7407


@pytest.mark.parametrize("change,word", [
    (dict(hybrid_override_pattern="MEMEM-EME"), "letters"),
    (dict(hybrid_override_pattern="MEMEM*EM"), "8 letters"),
    (dict(layer_types=["mamba"] * 9), "disagrees"),
    (dict(mamba_num_heads=6, n_groups=4), "multiple of n_groups"),
    (dict(mlp_hidden_act="gelu"), "two-matrix expert"),
    (dict(n_group=4, topk_group=2), "n_group"),
], ids=["letter", "length", "layer_types", "heads_groups", "act", "n_group"])
def test_from_hf_config_refuses_what_it_would_serve_as_another_model(
        change, word):
    cfg = dict(hf_dict(tiny()), **change)
    with pytest.raises(ValueError, match=word):
        ModelConfig.from_hf_config(cfg)


def test_the_tiny_preset_is_what_from_hf_config_makes_of_its_spelling():
    cfg = tiny()
    got = ModelConfig.from_hf_config(hf_dict(cfg), name=cfg.name,
                                     dtype="float32")
    assert got == cfg


def test_a_model_without_a_state_refuses_the_hybrid_fields():
    with pytest.raises(ValueError, match="mixer_types"):
        ModelConfig(expert_act="relu2")
    with pytest.raises(ValueError, match="two-matrix"):
        tiny(expert_act="")
    with pytest.raises(NotImplementedError, match="hybrid"):
        cfg = tiny()
        llama.prefill_batch(cfg, {}, jnp.zeros((1, 4), jnp.int32), None,
                            None, None, None, page_size=PS)


def test_the_benchmark_keeps_a_copy_of_the_reference():
    assert filecmp.cmp(
        os.path.join(REPO, "dynamo_tpu/models/reference/nemotron_h.py"),
        os.path.join(REPO, "benchmarks/chip/reference/nemotron_h.py"),
        shallow=False)
