"""MiMo-V2's structure at a toy size (`tiny-mimo-v2-debug`, and its share
`tiny-mimo-v2-ep4-debug`) against its float32 reference
(dynamo_tpu/models/reference/mimo_v2.py): sliding and full attention layers
mixed 5 : 1 behind a dense layer, KV heads a kind, keys wider than values, a
rotary over a third of a head's lanes with a base a kind, a learned sink in
the sliding softmax, the heads' outputs under a value scale, sigmoid-routed
experts under a selection bias with a share held; the run of sliding layers
scanned inside the period scan; the kernels at K != V widths with and
without a sink; the pools by kind; the refusals of `from_hf_config`.
Tolerances: tests/mimo_v2_common.py.
"""

import filecmp
import functools
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dynamo_tpu.engine.kv_cache import (KVCacheSpec, alloc_kv_pages,
                                        window_ring_pages)
from dynamo_tpu.models import llama, loader
from dynamo_tpu.models.config import ModelConfig
from dynamo_tpu.models.reference import mimo_v2 as ref
from dynamo_tpu.ops import attention as att
from dynamo_tpu.ops import pallas_attention as pa

from mimo_v2_common import (ATOL, FULL, RTOL, SLIDING, hf_dict,
                            seeded_params, share_of, tiny)

PS = 4       # page size: the window's edges (8 back) fall inside pages
CHUNK = 8
REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
CUT = os.path.join(REPO, "benchmarks/chip/configs/mimo-v2.5-w8a8-ep16-1chip")
TOKENS = [int(t) for t in np.random.default_rng(0).integers(1, 500, 45)]
# each a model that differs from the served one in ONE mechanism
MECHANISMS = ("no_sink", "value_scale_1", "rotary_all_lanes", "one_theta",
              "no_select_bias", "kv_heads_of_full", "window_127")


def _reference(cfg, p, tokens, **kw):
    return ref.forward(ref.Config.from_hf(hf_dict(cfg)), ref.dequantize(p),
                       jnp.asarray(tokens), **kw)


def _pools(cfg, num_pages=32, slots=2, ahead=CHUNK):
    spec = KVCacheSpec.from_model(cfg, num_pages=num_pages, page_size=PS,
                                  window_slots=slots, window_ahead=ahead)
    return spec, alloc_kv_pages(spec)


@functools.lru_cache(maxsize=None)
def _programs(cfg):
    """The chunk and decode programs of `cfg`, compiled once: run eagerly
    every call would trace and compile its scans again."""
    return (jax.jit(functools.partial(llama.prefill_chunk, cfg,
                                      page_size=PS)),
            jax.jit(functools.partial(llama.decode_step, cfg, page_size=PS)))


def _run_program(cfg, p, tokens, n_chunked=40):
    """The serving path's forward functions through both pools: the prompt
    in 8-token chunks (5 of them: the ring of 5 pages = 20 rows is written
    over twice), then decode steps in a batch of two slots of which one is
    empty. Returns {position: logits}."""
    spec, (kp, vp) = _pools(cfg)
    assert spec.ring_pages == window_ring_pages(8, CHUNK, PS) == 5
    n_pages = -(-len(tokens) // PS)
    pages = llama.ByKind(
        jnp.arange(1, 1 + n_pages + 1, dtype=jnp.int32).at[-1].set(0),
        jnp.arange(1, 1 + spec.ring_pages, dtype=jnp.int32))
    toks = jnp.asarray(tokens, jnp.int32)
    got = {}
    chunk, decode = _programs(cfg)
    for start in range(0, n_chunked, CHUNK):
        out = chunk(p, toks[start:start + CHUNK], jnp.int32(start),
                    jnp.int32(CHUNK), kp, vp, pages)
        kp, vp = out.k_pages, out.v_pages
        got[start + CHUNK - 1] = out.last_logits
    tables = llama.ByKind(
        jnp.stack([pages.full, jnp.zeros_like(pages.full)]),
        jnp.stack([pages.window, jnp.zeros_like(pages.window)]))
    for pos in range(n_chunked, len(tokens)):
        out = decode(p, jnp.asarray([tokens[pos], 0], jnp.int32),
                     jnp.asarray([pos, 0], jnp.int32), tables,
                     jnp.asarray([pos + 1, 1], jnp.int32), kp, vp)
        kp, vp = out.k_pages, out.v_pages
        got[pos] = out.logits[0]
    return got


def _worst(got, want):
    return max(float(np.max(np.abs(np.asarray(v) - np.asarray(want[pos]))))
               for pos, v in got.items())


@pytest.fixture(scope="module")
def model():
    cfg = tiny()
    return cfg, seeded_params(cfg)


@pytest.fixture(scope="module")
def served(model):
    """{position: logits} of the chunked prefill and the decode steps."""
    cfg, p = model
    return _run_program(cfg, p, TOKENS)


def test_chunked_prefill_then_decode_matches_reference(model, served):
    """Logits at contexts past window + chunk (a sliding layer's pages
    handed back and reused twice) against the reference's full forward."""
    cfg, p = model
    want = _reference(cfg, p, TOKENS)
    assert sorted(served) == [7, 15, 23, 31, 39, 40, 41, 42, 43, 44]
    for pos, logits in served.items():
        np.testing.assert_allclose(logits, want[pos], rtol=RTOL, atol=ATOL)


@pytest.mark.parametrize("variant", MECHANISMS)
def test_each_mechanism_is_seen(model, served, variant):
    """Dropping the sink, the value scale, the partial rotary, the second
    rotary base, the selection bias, the KV-head difference or a key of the
    window is another model: it fails the comparison by 50x its tolerance."""
    cfg, p = model
    wrong = _reference(cfg, p, TOKENS, variant=variant)
    assert _worst(served, wrong) > 50 * ATOL, variant


def test_reference_in_blocks_is_the_reference(model):
    cfg, p = model
    np.testing.assert_allclose(_reference(cfg, p, TOKENS, q_block=16),
                               _reference(cfg, p, TOKENS), rtol=1e-5,
                               atol=1e-5)


@pytest.mark.parametrize("s", [8, 16, 20],
                         ids=["in_window", "over_window", "whole_ring"])
def test_whole_prompt_prefill_matches_reference(model, s):
    """A whole prompt in one program: a bucket inside the window (the
    sliding layers' softmax takes the sink through prefill_attention) and
    buckets past it (the rows just written are attended as one chunk by the
    windowed paged op), each with padding rows past the prompt's end."""
    cfg, p = model
    spec, (kp, vp) = _pools(cfg)
    n = s - 3  # the last page holds a valid row and three padding rows
    toks = jnp.asarray(TOKENS[:n] + [0] * (s - n), jnp.int32)
    pages = llama.ByKind(jnp.arange(1, 1 + s // PS, dtype=jnp.int32),
                         jnp.arange(1, 1 + spec.ring_pages, dtype=jnp.int32))
    out = llama.prefill(cfg, p, toks, jnp.int32(n), kp, vp, pages,
                        page_size=PS)
    want = _reference(cfg, p, TOKENS[:n])
    np.testing.assert_allclose(out.last_logits, want[n - 1], rtol=RTOL,
                               atol=ATOL)


def test_mixed_step_matches_reference_and_leaves_other_pages_alone(model):
    """A decode row at context 41 and another sequence's chunk at 24-31
    (six valid rows and two of padding) beside an EMPTY slot in ONE mixed
    step, each over its own table and ring: both sequences' logits are the
    reference's, and every page that is neither sequence's own nor the
    trash page is bit for bit what it was, in all four pools."""
    cfg, p = model
    other = [int(t) for t in np.random.default_rng(1).integers(1, 500, 30)]
    want_a = _reference(cfg, p, TOKENS[:41])
    want_b = _reference(cfg, p, other)
    spec, (kp, vp) = _pools(cfg, num_pages=64, slots=3)
    w = spec.ring_pages

    def pages_of(first, ring_first, n_tokens):
        n = n_tokens // PS + 2
        return llama.ByKind(
            jnp.arange(first, first + n, dtype=jnp.int32).at[-2:].set(0),
            jnp.arange(ring_first, ring_first + w, dtype=jnp.int32))

    pa_, pb = pages_of(1, 1, 44), pages_of(20, 1 + w, 32)
    chunk, _ = _programs(cfg)
    for toks, pg, upto in ((TOKENS, pa_, 40), (other + [0, 0], pb, 24)):
        for start in range(0, upto, CHUNK):
            out = chunk(p, jnp.asarray(toks[start:start + CHUNK], jnp.int32),
                        jnp.int32(start), jnp.int32(CHUNK), kp, vp, pg)
            kp, vp = out.k_pages, out.v_pages
    # rows no sequence owns: a pattern that must survive the step
    rng = np.random.default_rng(9)
    own = {"full": set(range(1, 13)) | set(range(20, 30)),
           "window": set(range(1, 1 + 2 * w))}
    marked = []
    for pools in (kp, vp):
        out_pools = []
        for kind, pool in zip(("full", "window"), pools):
            mark = jnp.asarray(rng.standard_normal(pool.shape), pool.dtype)
            keep = np.zeros(pool.shape[1], bool)
            keep[sorted(own[kind])] = True
            out_pools.append(jnp.where(keep[None, :, None, None], pool, mark))
        marked.append(llama.ByKind(*out_pools))
    kp, vp = marked
    pad = pa_.full.shape[0]
    tables = llama.ByKind(
        jnp.stack([pa_.full, jnp.zeros((pad,), jnp.int32)]),
        jnp.stack([pa_.window, jnp.zeros((w,), jnp.int32)]))
    out = llama.mixed_step(
        cfg, p, jnp.asarray([TOKENS[40], 0], jnp.int32),
        jnp.asarray([40, 0], jnp.int32), tables,
        jnp.asarray([41, 1], jnp.int32),
        jnp.asarray(other[24:30] + [0, 0], jnp.int32), jnp.int32(24),
        jnp.int32(6), pb, kp, vp, page_size=PS)
    np.testing.assert_allclose(out.logits[0], want_a[40], rtol=RTOL,
                               atol=ATOL)
    np.testing.assert_allclose(out.chunk_logits, want_b[29], rtol=RTOL,
                               atol=ATOL)
    for before, after in ((kp, out.k_pages), (vp, out.v_pages)):
        for kind, b, a in zip(("full", "window"), before, after):
            others = [i for i in range(1, b.shape[1]) if i not in own[kind]]
            assert np.array_equal(np.asarray(b)[:, others],
                                  np.asarray(a)[:, others]), kind
    st = dict(zip(llama.moe_ops.MOE_STATS, np.asarray(out.moe_stats)))
    assert st["layer_steps"] == 6  # the dense layer counts nothing
    assert st["assignments"] == 6 * 7 * cfg.num_experts_per_tok


def test_runs_of_a_kind_are_scanned_inside_the_period_scan():
    """1 dense + two whole periods [sliding x 3, full] + a period cut short
    (two sliding layers): the scan over periods, the scan over each run of
    sliding layers inside it and the tail's, against the reference, which
    runs the layers one after another; and the program holds one layer
    body a run, not one a layer."""
    kinds = (FULL,) + ((SLIDING,) * 3 + (FULL,)) * 2 + (SLIDING,) * 2
    cfg = tiny(num_layers=11, layer_types=kinds)
    assert cfg.kind_period == 4
    assert llama._kind_runs(kinds[1:5]) == ((SLIDING, 0, 3), (FULL, 3, 1))
    p = seeded_params(cfg, seed=5)
    assert p["wq"].shape[0] == 2 and p["win.wk"].shape[:3] == (8, 64, 2)
    want = _reference(cfg, p, TOKENS[:28])
    got = _run_program(cfg, p, TOKENS[:28], n_chunked=24)
    for pos, logits in got.items():
        np.testing.assert_allclose(logits, want[pos], rtol=RTOL, atol=ATOL)
    spec, (kp, vp) = _pools(cfg)
    text = jax.jit(lambda *a: llama.decode_step(cfg, *a, page_size=PS)).lower(
        p, jnp.zeros((2,), jnp.int32), jnp.zeros((2,), jnp.int32),
        llama.ByKind(jnp.zeros((2, 9), jnp.int32),
                     jnp.zeros((2, spec.ring_pages), jnp.int32)),
        jnp.ones((2,), jnp.int32), kp, vp).as_text()
    # the periods' scan, a sliding run's inside it, the tail's run
    assert text.count("stablehlo.while") == 3


# ------------------------------------------------------- the shares add up --

def test_the_four_shares_expert_layers_sum_to_the_uncut_reference(model):
    """With 4 of 16 experts held, the four shares' expert-layer results
    (the program's grouped layer, each over its own slice of the weights,
    routing over all 16) sum to the uncut reference's expert layer; and a
    whole forward of a share is the reference's given that share."""
    cfg, p = model
    rc = ref.Config.from_hf(hf_dict(cfg))
    x = jnp.asarray(np.random.default_rng(4).standard_normal((11, 64)),
                    jnp.float32)
    lp = {k: v[2] for k, v in p.items()
          if k.startswith(("router", "moe_w_"))}
    with jax.default_matmul_precision("highest"):
        want = ref.experts(rc, ref.dequantize(lp), x)
    total = 0.0
    for first in (0, 4, 8, 12):
        share = tiny("tiny-mimo-v2-ep4-debug", local_expert_offset=first)
        held = dict(share_of({k: v[None] for k, v in lp.items()}, first, 4))
        y, stats = llama._mlp(share, {k: v[0] for k, v in held.items()}, x)
        total = total + y
        assert int(stats[llama.moe_ops.MOE_STATS.index("layer_steps")]) == 1
    np.testing.assert_allclose(total, want, rtol=RTOL, atol=ATOL)
    share = tiny("tiny-mimo-v2-ep4-debug")
    sp = share_of(p, 4, 4)
    got = _run_program(share, sp, TOKENS[:28], n_chunked=24)
    want = ref.forward(ref.Config.from_hf(hf_dict(share)), ref.dequantize(sp),
                       jnp.asarray(TOKENS[:28]), share=ref.Share(4, 4))
    for pos, logits in got.items():
        np.testing.assert_allclose(logits, want[pos], rtol=RTOL, atol=ATOL)


# ---------------------------------------- kernels at K != V widths, a sink --

def _kv(rng, pages, ps, n_kv, dk, dv):
    return (jnp.asarray(rng.standard_normal((pages, ps, n_kv * dk)),
                        jnp.float32),
            jnp.asarray(rng.standard_normal((pages, ps, n_kv * dv)),
                        jnp.float32))


def _sink(rng, heads, on):
    return ({"sink": jnp.asarray(rng.normal(1.0, 1.0, heads), jnp.float32)}
            if on else {})


@pytest.mark.parametrize("sink", [False, True], ids=["plain", "sink"])
@pytest.mark.parametrize("group", [8, 16])
def test_decode_kernel_at_wider_keys_is_the_composition(group, sink):
    """The decode kernel (interpret mode) over K rows of 192 lanes a head
    and V rows of 128, at 8 and 16 query heads a KV head, under a static
    window of 40 with contexts under, at and over it (and an empty slot),
    with and without a sink, against the XLA twin; the sink changes every
    live row."""
    rng = np.random.default_rng(group + sink)
    n_kv, dk, dv, ps, window = 2, 192, 128, 16, 40
    kp, vp = _kv(rng, 64, ps, n_kv, dk, dv)
    q = jnp.asarray(rng.standard_normal((5, n_kv * group, dk)), jnp.float32)
    tables = jnp.asarray(rng.permutation(np.arange(1, 61))[:50].reshape(5, 10),
                         jnp.int32)
    ctx = jnp.asarray([1, 37, 40, 41, 150], jnp.int32)
    sk = _sink(rng, n_kv * group, sink)
    kw = dict(page_size=ps, num_kv_heads=n_kv, window=window)
    with att.attention_context("xla", None, 1):
        want = att.paged_attention_decode(q, kp, vp, tables, ctx, **kw, **sk)
        plain = att.paged_attention_decode(q, kp, vp, tables, ctx, **kw)
    with att.attention_context("pallas_interpret", None, 1):
        got = att.paged_attention_decode(
            q, kp, vp, tables, ctx, **kw, **sk,
            kernel_lens=ctx.at[0].set(0))
    assert got.shape == (5, n_kv * group, dv)
    np.testing.assert_allclose(got[1:], want[1:], rtol=2e-5, atol=2e-5)
    assert not np.any(np.asarray(got[0]))  # the empty slot: zeros, no copy
    assert sink == (not np.allclose(want[1:], plain[1:], atol=1e-3))


@pytest.mark.parametrize("group,decode,window,sink", [
    (8, 3, 40, True), (16, 3, 40, True), (16, 0, 40, True), (16, 3, 40, False),
    (16, 3, 0, False), (8, 0, 0, True)])
def test_ragged_kernel_at_wider_keys_is_the_composition(group, decode, window,
                                                        sink):
    """The ragged kernel at K 192 / V 128 lanes a head: decode rows and a
    chunk (or the chunk alone: chunk_attention's route under a window or a
    sink), windowed and not, with and without a sink."""
    rng = np.random.default_rng(10 + group + decode + window + sink)
    n_kv, dk, dv, ps, c = 2, 192, 128, 16, 32
    kp, vp = _kv(rng, 96, ps, n_kv, dk, dv)
    q = jnp.asarray(rng.standard_normal((decode + c, n_kv * group, dk)),
                    jnp.float32)
    tables = jnp.asarray(
        rng.permutation(np.arange(1, 46)).reshape(3, 15)[:decode], jnp.int32)
    ctx = jnp.asarray([5, 41, 200][:decode], jnp.int32)
    p_pages = jnp.asarray(rng.permutation(np.arange(46, 96))[:20], jnp.int32)
    start = 13 * ps  # the first query's reach starts mid-page
    sk = _sink(rng, n_kv * group, sink)
    kw = dict(page_size=ps, num_kv_heads=n_kv, **sk,
              **({"window": window} if window else {}))
    outs = {}
    for backend in ("xla", "pallas_interpret"):
        with att.attention_context(backend, None, 1):
            if decode:
                outs[backend] = att.ragged_mixed_attention(
                    q, kp, vp, tables, ctx, p_pages, start,
                    num_decode=decode, **kw)
            else:
                outs[backend] = att.chunk_attention(q, kp, vp, p_pages,
                                                    start, **kw)
    assert outs["xla"].shape == (decode + c, n_kv * group, dv)
    np.testing.assert_allclose(outs["pallas_interpret"], outs["xla"],
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("group,sink", [(8, True), (16, False), (16, True)])
def test_flash_and_chunk_kernels_at_wider_keys_are_the_composition(group,
                                                                   sink):
    """The whole-prompt flash kernel (K 192 / V 128 lanes a head, with a
    sink a head or without) and, without a sink, the unwindowed chunk
    kernel over pages of both widths, each against its XLA twin."""
    rng = np.random.default_rng(group + 2 * sink)
    n_kv, dk, dv, s = 2, 192, 128, 48
    q = jnp.asarray(rng.standard_normal((s, n_kv * group, dk)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((s, n_kv, dk)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((s, n_kv, dv)), jnp.float32)
    sk = _sink(rng, n_kv * group, sink)
    want = att.prefill_attention_xla(q, k, v, 41, **sk)
    got = pa.prefill_attention(q, k, v, 41, interpret=True, **sk)
    assert got.shape == (s, n_kv * group, dv)
    np.testing.assert_allclose(got[:41], want[:41], rtol=2e-5, atol=2e-5)
    if sink:
        return
    kp, vp = _kv(rng, 32, 16, n_kv, dk, dv)
    pages = jnp.asarray(rng.permutation(np.arange(1, 32))[:12], jnp.int32)
    with att.attention_context("xla", None, 1):
        want = att.chunk_attention(q[:32], kp, vp, pages, 96, page_size=16,
                                   num_kv_heads=n_kv)
    with att.attention_context("pallas_interpret", None, 1):
        got = att.chunk_attention(q[:32], kp, vp, pages, 96, page_size=16,
                                  num_kv_heads=n_kv)
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)


def test_a_call_without_a_sink_traces_none_of_it():
    """The existing call sites (K = V width, no sink): the kernels' traces
    hold no sink operand and no `attn_sink` scope, with one they do."""
    rng = np.random.default_rng(0)
    kp, vp = _kv(rng, 8, 16, 2, 128, 128)
    q = jnp.zeros((2, 4, 128), jnp.float32)
    args = (q, kp, vp, jnp.ones((2, 3), jnp.int32), jnp.ones((2,), jnp.int32))

    def text(**kw):
        with att.attention_context("xla", None, 1):
            return str(jax.make_jaxpr(lambda *a: att.paged_attention_decode(
                *a, page_size=16, num_kv_heads=2, **kw))(*args))

    assert "exp" in text() and "attn_sink" not in text()
    with_sink = jax.jit(lambda *a: att.paged_attention_decode_xla(
        *a, page_size=16, num_kv_heads=2,
        sink=jnp.zeros((4,), jnp.float32))).lower(*args).as_text(
            debug_info=True)
    assert "attn_sink" in with_sink


# ------------------------------------------------------------ the two pools --

def test_pools_by_kind_at_the_published_sizes():
    """The cut at the cell's flags: full rows hold 4 KV heads (768 | 512
    lanes), ring rows 8 (1,536 | 1,024), 2,560 / 5,120 B a token a layer,
    rings of 25 pages; no head is padded to the other's width."""
    cfg = ModelConfig.from_model_name(CUT)
    spec = KVCacheSpec.from_model(cfg, 24576, 16, window_slots=65,
                                  window_ahead=256)
    assert spec.ring_pages == window_ring_pages(128, 256, 16) == 25
    assert spec.kind_kv_heads() == {"full": 4, "window": 8}
    assert spec.kind_lanes() == {"full": {"k": 768, "v": 512},
                                 "window": {"k": 1536, "v": 1024}}
    assert spec.shape == (3, 24576, 16, 768)
    assert spec.v_shape == (3, 24576, 16, 512)
    assert spec.window_shape == (10, 65 * 25 + 1, 16, 1536)
    assert spec.window_v_shape == (10, 65 * 25 + 1, 16, 1024)
    assert spec.bytes_per_token_by_kind() == {"full": 3 * 2560,
                                              "window": 10 * 5120}
    assert spec.bytes_per_token() == 7680
    with pytest.raises(ValueError, match="bf16 KV on one chip"):
        KVCacheSpec.from_model(cfg, 64, 16, kv_dtype="int8", window_slots=1)
    with pytest.raises(ValueError, match="bf16 KV on one chip"):
        KVCacheSpec.from_model(cfg, 64, 16, tensor_parallel=2, window_slots=1)


def test_the_seeded_loader_draws_the_new_leaves():
    """loader.random_quantized_params: a K/V stack a kind (quantized as wk
    / wv are), the sinks float32 and drawn (never the zero that would hide
    a dropped sink), the selection bias float32 zeros as for every
    sigmoid-routed model here."""
    from dynamo_tpu.models.quant import QTensor

    cfg = tiny("tiny-mimo-v2-ep4-debug")
    p = loader.random_quantized_params(cfg, seed=7, mode="w8a8")
    assert isinstance(p["win.wk"], QTensor) and isinstance(p["wv"], QTensor)
    assert p["win.wk"].q.shape == (5, 64, 2, 24)
    assert p["wv"].q.shape == (1, 64, 1, 16)
    assert p["win.wo"].q.shape == (5, 8, 16, 64)
    assert p["moe_w_up"].q.shape == (6, 4, 64, 32)
    sink = p["win.sink"]
    assert sink.dtype == np.float32 and sink.shape == (5, 8)
    assert np.all(np.abs(sink) <= 2.0) and np.std(sink) > 0.5
    assert p["router_bias"].dtype == np.float32
    assert "sink" not in p and "dense.sink" not in p
    with pytest.raises(NotImplementedError, match="layer_types"):
        loader.load_hf_safetensors(cfg, [])


# ---------------------------------------------------------- from_hf_config --

def _row(name="MiMo-V2.5") -> dict:
    with open(CATALOG) as f:
        for line in f:
            row = json.loads(line)
            if row["name"] == name:
                return row["config"]
    raise AssertionError(name)


def test_from_hf_config_loads_the_published_row_and_the_cut():
    cfg = ModelConfig.from_hf_config(_row())
    assert cfg.num_layers == 48 and cfg.first_k_dense == 1
    assert cfg.kind_layers(FULL) == 9 and cfg.kind_layers(SLIDING) == 39
    assert cfg.kind_heads(FULL) == cfg.kind_heads(SLIDING) == 64
    assert cfg.kind_kv_heads(FULL) == 4 and cfg.kind_kv_heads(SLIDING) == 8
    assert cfg.kv_by_kind and cfg.head_dim == 192
    assert cfg.value_head_dim == 128
    assert cfg.sliding_window == 128 and cfg.attn_gate == ""
    assert cfg.attn_sink_kinds == (SLIDING,)
    assert cfg.attn_value_scale == 0.707 and cfg.rms_norm_eps == 1e-5
    full, sliding = cfg.rope_by_kind
    assert full[:2] == (FULL, 1e7) and sliding[:2] == (SLIDING, 1e4)
    assert round(192 * full[2]) == round(192 * sliding[2]) == 64
    assert (cfg.moe_scoring, cfg.router_bias, cfg.n_group) == (
        "sigmoid", True, 1)
    assert cfg.num_experts == 256 and cfg.num_experts_per_tok == 8
    assert cfg.num_shared_experts == 0 and cfg.routed_scaling_factor == 1.0
    assert cfg.dense_intermediate_size == 16384 and cfg.moe_grouped
    # the first period behind the dense layer is cut short by one layer:
    # the kinds repeat with 6 from layer 6 on, not from layer 1
    assert cfg.layer_types[:6] == (FULL,) + (SLIDING,) * 4 + (FULL,)
    cut = ModelConfig.from_model_name(CUT)
    assert cut.num_layers == 13 and cut.kind_period == 6
    assert cut.layer_types == (FULL,) + ((SLIDING,) * 5 + (FULL,)) * 2
    assert (cut.num_experts, cut.held_experts, cut.vocab_size) == (
        256, 16, 19072)
    specs = llama.param_specs(cut)
    assert specs["wq"][0] == (2, 4096, 64, 192)
    assert specs["wk"][0] == (2, 4096, 4, 192)
    assert specs["wv"][0] == (2, 4096, 4, 128)
    assert specs["win.wk"][0] == (10, 4096, 8, 192)
    assert specs["win.wv"][0] == (10, 4096, 8, 128)
    assert specs["win.wo"][0] == (10, 64, 128, 4096)
    assert specs["win.sink"] == ((10, 64), "sink", 0.0)
    assert specs["dense.wk"][0] == (1, 4096, 4, 192)
    assert specs["dense.w_up"][0] == (1, 4096, 16384)
    assert specs["moe_w_gate"][0] == (12, 16, 4096, 2048)
    assert specs["router"][0] == (12, 4096, 256)
    assert "wg" not in specs and "sink" not in specs
    with open(os.path.join(CUT, "config.json")) as f:
        held = json.load(f)
    row = _row()
    changed = {k for k in row if held.get(k) != row[k]}
    with open(CUT + ".json") as f:
        assert changed == set(json.load(f)["reduced"])
    # the tiny presets are the same mapping
    for name in ("tiny-mimo-v2-debug", "tiny-mimo-v2-ep4-debug"):
        assert ModelConfig.from_hf_config(
            hf_dict(tiny(name)), name=name, dtype="float32") == tiny(name)


@pytest.mark.parametrize("change,word", [
    ({"add_full_attention_sink_bias": True}, "add_full_attention_sink_bias"),
    ({"attention_bias": True}, "attention_bias"),
    ({"n_group": 8}, "n_group"),
    ({"topk_group": 4}, "topk_group"),
    ({"n_shared_experts": 1}, "n_shared_experts"),
    ({"scoring_func": "softmax"}, "scoring_func"),
    ({"moe_layer_freq": [0, 1, 0] + [1] * 45}, "moe_layer_freq"),
    ({"swa_head_dim": 128}, "swa_head_dim"),
    ({"swa_v_head_dim": 64}, "swa_v_head_dim"),
    ({"swa_num_attention_heads": 32}, "swa_num_attention_heads"),
    ({"rope_scaling": {"rope_type": "yarn", "factor": 4.0}}, "rope_scaling"),
    ({"attention_chunk_size": 256}, "attention_chunk_size"),
    ({"hybrid_block_size": 4}, "hybrid_block_size"),
    ({"hybrid_layer_pattern": [0, 1] * 20}, "hybrid_layer_pattern"),
    ({"layer_types": ["full_attention"] * 48}, "layer_types"),
    ({"num_nextn_predict_layers": 3}, "multi-token-prediction"),
], ids=["sink_on_full", "attention_bias", "n_group", "topk_group",
        "shared_expert", "softmax_scores", "dense_behind_expert",
        "swa_head_dim", "swa_v_head_dim", "swa_heads", "rope_scaling",
        "chunk_size", "hybrid_block", "short_pattern",
        "layer_types_disagree", "mtp_layers"])
def test_from_hf_config_refuses_by_name_what_it_cannot_serve(change, word):
    with pytest.raises(ValueError, match=word):
        ModelConfig.from_hf_config({**_row(), **change})


def test_kinds_fields_without_layer_types_refuse():
    for kw in ({"kv_heads_sliding": 2}, {"attn_sink_kinds": (SLIDING,)},
               {"attn_value_scale": 0.5}):
        with pytest.raises(ValueError, match="without layer_types"):
            ModelConfig(**kw)
    with pytest.raises(ValueError, match="no layer is of"):
        tiny(layer_types=(FULL,) * 7, sliding_window=0, kv_heads_sliding=0,
             rope_by_kind=((FULL, 1e7, 1 / 3, None),))
    with pytest.raises(ValueError, match="values are as wide as keys"):
        tiny(v_head_dim=32)


def test_the_benchmark_keeps_a_copy_of_the_reference():
    assert filecmp.cmp(
        os.path.join(REPO, "dynamo_tpu/models/reference/mimo_v2.py"),
        os.path.join(REPO, "benchmarks/chip/reference/mimo_v2.py"),
        shallow=False)


def test_the_new_cost_file_counts_rows_by_the_kinds_own_widths():
    """kernel_costs/gqa_paged_attention_kv.py: bytes = rows x the kind's KV
    heads x (192 + 128) x 2, operations = pairs x 64 x 2 x (192 + 128)."""
    import importlib.util

    spec = importlib.util.spec_from_file_location(
        "gqa_kv", os.path.join(
            REPO, "benchmarks/chip/kernel_costs/gqa_paged_attention_kv.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    grew = {"metrics.attn_kinds.full.decode_kv_rows": 1000.0,
            "metrics.attn_kinds.window.decode_kv_rows": 128.0}
    got = mod.from_counters(lambda path: grew.get(path, 0.0), dict(
        which="decode", layers_full=3, layers_window=10, heads_full=64,
        heads_window=64, kv_heads_full=4, kv_heads_window=8, qk_dim=192,
        v_dim=128))
    assert got["bytes"] == 3 * 1000 * 2560 + 10 * 128 * 5120
    assert got["ops"] == (3 * 1000 + 10 * 128) * 64 * 2 * 320
    with open(os.path.join(REPO, "benchmarks/chip/layer_metrics",
                           "gqa_decode_attn_roofline.longreason.json")) as f:
        args = json.load(f)["args"]
    assert {k: args[k] for k in ("layers_full", "layers_window",
                                 "kv_heads_full", "kv_heads_window")} == {
        "layers_full": 3, "layers_window": 10, "kv_heads_full": 4,
        "kv_heads_window": 8}
