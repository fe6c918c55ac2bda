"""Chunked prefill: correctness vs full prefill + bounded decode gaps."""

import numpy as np
import pytest

from dynamo_tpu.engine.config import EngineConfig
from dynamo_tpu.engine.engine import Engine
from dynamo_tpu.engine.request import GenRequest


def _mk(chunk, **kw):
    base = dict(model="tiny-debug", page_size=4, num_pages=256,
                max_num_seqs=4, max_seq_len=256, prefill_chunk_tokens=chunk)
    base.update(kw)
    return Engine(EngineConfig(**base))


PROMPT = [(i * 11) % 300 + 1 for i in range(50)]


def test_chunked_matches_full_prefill_greedy():
    full = _mk(0).generate(GenRequest("f", PROMPT, max_tokens=10,
                                      temperature=0.0, ignore_eos=True))
    chunked = _mk(8).generate(GenRequest("c", PROMPT, max_tokens=10,
                                         temperature=0.0, ignore_eos=True))
    assert chunked == full


def test_chunked_matches_full_prefill_seeded_sampling():
    kw = dict(max_tokens=10, temperature=0.8, top_p=0.9, seed=123,
              ignore_eos=True)
    full = _mk(0).generate(GenRequest("f", PROMPT, **kw))
    chunked = _mk(8).generate(GenRequest("c", PROMPT, **kw))
    assert chunked == full


def test_decode_continues_between_chunks():
    """While a long prompt prefills chunk-by-chunk, an active stream keeps
    emitting tokens — the stall-bounding contract."""
    eng = _mk(8)
    eng.add_request(GenRequest("live", [1, 2, 3], max_tokens=64,
                               temperature=0.0, ignore_eos=True))
    eng.step()  # admit + first decode
    eng.add_request(GenRequest("long", PROMPT, max_tokens=4,
                               temperature=0.0, ignore_eos=True))
    # drive until the long prompt lands; count chunk steps that also decoded
    chunk_steps = decode_during_chunks = 0
    while eng._inflight is not None or any(
            r.request_id == "long" for r in eng.pending):
        evs = eng.step()
        if eng._inflight is not None:
            chunk_steps += 1
            if any(e.request_id == "live" and e.token_id >= 0 for e in evs):
                decode_during_chunks += 1
    assert chunk_steps >= 3, "prompt should take several chunks"
    # every chunk step must also have produced live-stream tokens
    assert decode_during_chunks >= chunk_steps - 1
    stats = eng.metrics.snapshot()
    assert stats["phases"]["prefill_chunk"]["count"] >= 3


def test_chunked_abort_mid_prefill_releases_pages():
    eng = _mk(8)
    free0 = eng.allocator.free_pages
    eng.add_request(GenRequest("long", PROMPT, max_tokens=4,
                               temperature=0.0, ignore_eos=True))
    eng.step()  # starts the inflight prefill
    assert eng._inflight is not None
    eng.abort_request("long")
    evs = eng.step()
    assert any(e.request_id == "long" and e.finish_reason == "abort"
               for e in evs)
    assert eng._inflight is None
    assert eng.allocator.free_pages == free0


def test_chunked_final_chunk_past_bucket_cap():
    """Regression: when the page-aligned bucket cap is NOT a chunk multiple,
    the padded final chunk used to overrun the page table and dynamic_slice
    clamped it into the wrong pages, silently corrupting the prompt KV."""
    prompt = [(i * 13) % 300 + 1 for i in range(26)]
    kw = dict(model="tiny-debug", page_size=4, num_pages=64, max_num_seqs=2,
              max_seq_len=28)  # cap 28 tokens = 7 pages, not a multiple of 8
    full = Engine(EngineConfig(prefill_chunk_tokens=0, **kw)).generate(
        GenRequest("f", prompt, max_tokens=2, temperature=0.0,
                   ignore_eos=True))
    chunked = Engine(EngineConfig(prefill_chunk_tokens=8, **kw)).generate(
        GenRequest("c", prompt, max_tokens=2, temperature=0.0,
                   ignore_eos=True))
    assert chunked == full


def test_chunked_engine_with_pallas_chunk_kernel():
    """End-to-end: engine chunked prefill through the Pallas flash kernel
    (interpret mode) produces the same tokens as the XLA chunk path.

    Uses a model whose KV*D = 128 so the alignment gate actually admits the
    kernel (tiny-debug's 64 lanes would silently fall back to XLA and the
    test would compare the XLA path to itself)."""
    from dynamo_tpu.models.config import ModelConfig

    mcfg = ModelConfig(name="chunk-kernel-test", vocab_size=256,
                       hidden_size=64, intermediate_size=128, num_layers=2,
                       num_heads=4, num_kv_heads=2, head_dim=64,
                       dtype="float32")
    prompt = [(i * 11) % 200 + 1 for i in range(50)]
    kw = dict(model="tiny-debug", page_size=4, num_pages=256, max_num_seqs=4,
              max_seq_len=256, prefill_chunk_tokens=8)
    ref = Engine(EngineConfig(**kw), model_cfg=mcfg).generate(
        GenRequest("x", prompt, max_tokens=8, temperature=0.0,
                   ignore_eos=True))
    out = Engine(EngineConfig(**kw, attention_backend="pallas_interpret"),
                 model_cfg=mcfg).generate(
        GenRequest("x", prompt, max_tokens=8, temperature=0.0,
                   ignore_eos=True))
    assert out == ref


def test_chunk_backend_follows_the_scoped_backend(monkeypatch):
    """Chunk attention follows the engine's scoped attention backend like
    the other ops, and nothing else: a kernel backend calls the kernel,
    `xla` the gather path."""
    import numpy as np
    import jax.numpy as jnp

    from dynamo_tpu.ops import attention as att
    from dynamo_tpu.ops import pallas_attention as pa

    rng = np.random.default_rng(21)
    ps, n_kv, d, h = 16, 2, 64, 4
    kp = jnp.asarray(rng.normal(size=(16, ps, n_kv * d)), jnp.float32)
    pages = jnp.asarray([1, 2, 3, 4], jnp.int32)
    q = jnp.asarray(rng.normal(size=(16, h, d)), jnp.float32)

    calls = []
    real = pa.chunk_prefill_attention

    def spy(*a, **k):
        calls.append(1)
        return real(*a, **k)

    monkeypatch.setattr(pa, "chunk_prefill_attention", spy)
    for backend, kernel in (("xla", False), ("pallas_interpret", True)):
        del calls[:]
        with att.attention_context(backend, None):
            att.chunk_attention(q, kp, kp, pages, 16, page_size=ps)
        assert bool(calls) is kernel


def test_chunk_kernel_int8_pools_stay_gated_until_validated(monkeypatch):
    """The int8 dequant-in-chunk path has a gate of its own: int8 pools
    keep the XLA path on every backend until
    CHUNK_KERNEL_INT8_HW_VALIDATED flips (ROADMAP S3/S4: judged on a
    cell)."""
    import numpy as np
    import jax.numpy as jnp

    from dynamo_tpu.ops import attention as att
    from dynamo_tpu.ops import pallas_attention as pa

    rng = np.random.default_rng(13)
    ps, n_kv, d, h = 4, 2, 64, 4
    kf = jnp.asarray(rng.normal(size=(16 * ps, n_kv, d)), jnp.float32)
    w = att.kv_lane_width(n_kv, d, True)
    k8 = att.pack_kv_rows(kf, w).reshape(16, ps, w)
    pages = jnp.asarray([1, 2, 3, 4], jnp.int32)
    q = jnp.asarray(rng.normal(size=(16, h, d)), jnp.float32)

    calls = []
    real = pa.chunk_prefill_attention

    def spy(*a, **k):
        calls.append(1)
        return real(*a, **k)

    monkeypatch.setattr(pa, "chunk_prefill_attention", spy)
    with att.attention_context("pallas_interpret", None):
        monkeypatch.setattr(pa, "CHUNK_KERNEL_INT8_HW_VALIDATED", False)
        att.chunk_attention(q, k8, k8, pages, 16, page_size=ps,
                            num_kv_heads=n_kv)
        assert not calls  # int8 not validated: XLA path
        monkeypatch.setattr(pa, "CHUNK_KERNEL_INT8_HW_VALIDATED", True)
        att.chunk_attention(q, k8, k8, pages, 16, page_size=ps,
                            num_kv_heads=n_kv)
        assert calls  # int8 validated: kernel follows the backend
