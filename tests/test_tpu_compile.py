"""The ragged kernel at the benchmark cells' mixed-step shapes, and the
decode kernel at their decode batch, compiled by the TPU's own compiler for
a v5e that is described, not attached.

Interpret mode proves a kernel's arithmetic on the CPU; Mosaic refuses what
it cannot tile or fit in VMEM only when it compiles. The compiler is
installed in the sandbox, so this guards the default mixed step of both
chat cells (PR 26) and every cell's decode window (PR 28) at no chip time. Nothing runs: no result, no timing.

The topology is described inside a fixture and in this file only (one
process may load libtpu; see the on-chip-measurement guide, section 2)."""

import functools
import os

import pytest

PAGE, HEAD_DIM, SLOTS, CHUNK, POOL_PAGES = 16, 128, 32, 256, 4096


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no libtpu here, or it is held elsewhere
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # the session's compile cache (conftest.py) could write what libtpu
    # compiles and never read it (DeserializeLoadedExecutable: unimplemented)
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", True)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding

    return SingleDeviceSharding(topo.devices[0])


@pytest.mark.parametrize("width", [128, 143],
                         ids=["table128", "bucket2048_table143"])
@pytest.mark.parametrize("heads,kv_heads", [(28, 4), (32, 8)],
                         ids=["qwen_28q4kv", "mixtral_32q8kv"])
def test_ragged_kernel_compiles_for_v5e_at_cell_shapes(one_chip, heads,
                                                       kv_heads, width):
    import jax
    import jax.numpy as jnp

    from dynamo_tpu.ops import ragged_attention as ra

    def arg(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    pool = arg((POOL_PAGES, PAGE, kv_heads * HEAD_DIM), jnp.bfloat16)
    fn = jax.jit(functools.partial(
        ra.ragged_paged_attention, page_size=PAGE, num_kv_heads=kv_heads,
        num_decode=SLOTS))
    compiled = fn.lower(
        arg((SLOTS + CHUNK, heads, HEAD_DIM), jnp.bfloat16), pool, pool,
        arg((SLOTS + 1, width), jnp.int32), arg((SLOTS + 1,), jnp.int32),
        arg((SLOTS + 1,), jnp.int32)).compile()
    assert "tpu_custom_call" in compiled.as_text()


def _layer_metric_patterns(*names):
    """The trace patterns of benchmarks/chip/layer_metrics/<name>.json, read
    from the benchmark's own files."""
    import json

    root = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "benchmarks", "chip", "layer_metrics")
    out = []
    for name in names:
        with open(os.path.join(root, name + ".json")) as f:
            out.append(json.load(f)["args"]["pattern"])
    return out


# the mixed step of every cell that runs the ragged kernel (the docqa
# cell's selects its rows elsewhere): slots, query heads, KV heads, lanes a
# head, table width handed (max(decode table, chunk table): --max-seq-len /
# 16, + 15 where the chunk's table is the wider), static window, V lanes
# (0: MLA's row stored once), and the layer metrics that find the kernel
RAGGED_CELLS = {
    "qwen7b-chat-r80": (32, 28, 4, 128, 128 + 15, 0, None,
                        ("attn_kernel_busy_pct.chat",)),
    "mixtral-chat-r80": (32, 32, 8, 128, 128 + 15, 0, None,
                         ("attn_kernel_busy_pct.chat",)),
    "kimi-k2-agent-r80": (64, 64, 1, 640, 384 + 15, 0, 0,
                          ("mla_mixed_attn_roofline.agent",
                           "mla_attn_busy_pct.agent")),
    "laguna-s-longmix-r80.full": (64, 48, 8, 128, 2048 + 15, 0, None,
                                  ("gqa_mixed_attn_roofline.longmix",
                                   "full_attn_busy_pct.longmix")),
    "laguna-s-longmix-r80.window": (64, 72, 8, 128, 49, 512, None,
                                    ("gqa_mixed_attn_roofline.longmix",
                                     "window_attn_busy_pct.longmix")),
    "nemotron3-nano-reason-r80": (64, 32, 2, 128, 384 + 15, 0, None, ()),
    "falcon-h1-rewrite-r80": (64, 20, 4, 128, 384 + 15, 0, None,
                              ("gqa_mixed_attn_roofline.rewrite",
                               "full_attn_busy_pct.rewrite")),
}


@pytest.mark.parametrize("cell", sorted(RAGGED_CELLS))
def test_ragged_kernel_is_found_by_the_benchmark_at_every_cell(one_chip,
                                                                cell):
    """The ragged kernel (PR 45: one grid step a query block, a loop with a
    dynamic trip count over the block's own KV blocks) compiles for a
    described v5e at every cell's mixed step, its grid has ONE dimension,
    and its custom call's text, the name a trace gives it, is matched by
    the patterns of the benchmark's own layer metrics: the result stays
    `bf16[query blocks, 8, heads, lanes]`."""
    import re

    import jax
    import jax.numpy as jnp

    from dynamo_tpu.ops import ragged_attention as ra

    slots, heads, kv_heads, lanes, width, window, v_lanes, metrics = \
        RAGGED_CELLS[cell]

    def arg(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    fn = jax.jit(functools.partial(
        ra.ragged_paged_attention, page_size=PAGE, num_kv_heads=kv_heads,
        num_decode=slots, window=window))
    args = (arg((slots + CHUNK, heads, lanes), jnp.bfloat16),
            arg((POOL_PAGES, PAGE, kv_heads * lanes), jnp.bfloat16),
            arg((POOL_PAGES, PAGE,
                 kv_heads * lanes if v_lanes is None else v_lanes),
                jnp.bfloat16),
            arg((slots + 1, width), jnp.int32), arg((slots + 1,), jnp.int32),
            arg((slots + 1,), jnp.int32))

    def pallas_calls(jaxpr):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "pallas_call":
                yield eqn
            for sub in jax.core.jaxprs_in_params(eqn.params):
                yield from pallas_calls(sub)

    calls = list(pallas_calls(jax.make_jaxpr(fn)(*args).jaxpr))
    assert len(calls) == 1
    assert calls[0].params["grid_mapping"].grid == (slots + CHUNK // 8,)

    lines = [ln.strip() for ln in fn.lower(*args).compile().as_text()
             .splitlines() if "tpu_custom_call" in ln]
    assert len(lines) == 1
    assert f" = bf16[{slots + CHUNK // 8},8,{heads},{lanes}]{{" in lines[0]
    for pattern in _layer_metric_patterns(*metrics):
        assert re.search(pattern, lines[0]), (pattern, lines[0][:200])


@pytest.mark.parametrize(
    "slots,heads,kv_heads,head_dim,width,pool_dtype,v_lanes",
    [(32, 28, 4, 128, 128, "bfloat16", None),
     (32, 32, 8, 128, 128, "bfloat16", None),
     (64, 64, 1, 640, 384, "bfloat16", 0),
     (32, 28, 4, 128, 128, "int8", None)],
    ids=["qwen_28q4kv", "mixtral_32q8kv", "kimi_64q_one_640_lane_row",
         "qwen_int8kv"])
def test_decode_kernel_compiles_for_v5e_at_cell_shapes(
        one_chip, slots, heads, kv_heads, head_dim, width, pool_dtype,
        v_lanes):
    """The decode window's kernel (one grid step a slot, a loop with a
    dynamic trip count over the slot's own superblocks, PR 28) at the three
    cells' batch and table, and over an int8-KV pool."""
    import jax
    import jax.numpy as jnp

    from dynamo_tpu.ops import attention as att
    from dynamo_tpu.ops import pallas_attention as pa

    def arg(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    dtype = jnp.dtype(pool_dtype)
    lanes = att.kv_lane_width(kv_heads, head_dim, dtype == jnp.int8)
    fn = jax.jit(functools.partial(
        pa.paged_attention_decode, page_size=PAGE, num_kv_heads=kv_heads))
    compiled = fn.lower(
        arg((slots, heads, head_dim), jnp.bfloat16),
        arg((POOL_PAGES, PAGE, lanes), dtype),
        arg((POOL_PAGES, PAGE, lanes if v_lanes is None else v_lanes), dtype),
        arg((slots, width), jnp.int32), arg((slots,), jnp.int32)).compile()
    text = compiled.as_text()
    assert text.count("tpu_custom_call") == 1
    # the benchmark's trace readers match the kernel by this output
    assert f"bf16[{slots},{heads},{head_dim}]" in text


@pytest.mark.parametrize("tokens,rungs", [(64, (32, 128, 512)),
                                          (64 + CHUNK, (256, 2560))],
                         ids=["decode_64_slots", "mixed_64_slots_256_chunk"])
def test_kimi_expert_layer_compiles_with_a_ragged_dot_a_rung(one_chip, tokens,
                                                             rungs):
    """The Kimi share's expert layers (8 x 24 of 384 experts, 7168 x 2048,
    w8a8, top-8; the layer scan hands the whole stack and the layer's
    index) at the cell's decode and mixed steps: every rung of the row
    ladder (PR 31) keeps XLA's own grouped matmul, three instructions NAMED
    `%ragged-dot...` with the rung's row count, inside one conditional, and
    none copies the stack. The trace names an operation by that text, and
    benchmarks/chip/layer_metrics/moe_*.agent.json find the expert matmuls
    by `^%ragged-dot`: a kernel of our own in their place would read as
    nothing there (PERF.md section 7)."""
    import re

    import jax
    import jax.numpy as jnp

    from dynamo_tpu.models import quant
    from dynamo_tpu.ops import moe

    layers, held, experts, hidden, width, k = 8, 24, 384, 7168, 2048, 8

    def arg(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    def stack(a, b):
        return quant.QTensorA8(arg((layers, held, a, b), jnp.int8),
                               arg((layers, held, 1, b), jnp.float32))

    def expert_layers(x, topi, w, wg, wu, wd):
        def body(carry, idx):
            x, counts = carry
            y, c = moe.moe_mlp_grouped(
                x, topi, w, wg, wu, wd, expert_offset=2 * held,
                num_experts=experts, layer=idx)
            return (x + y, counts + c), None
        zero = jnp.zeros((len(moe.MOE_STATS),), jnp.int32)
        return jax.lax.scan(body, (x, zero), jnp.arange(layers))[0]

    text = jax.jit(expert_layers).lower(
        arg((tokens, hidden), jnp.bfloat16), arg((tokens, k), jnp.int32),
        arg((tokens, k), jnp.float32), stack(hidden, width),
        stack(hidden, width), stack(width, hidden)).compile().as_text()
    assert moe.row_rungs(tokens * k, held / experts) == (0,) + rungs
    named = re.findall(r"^\s*(?:ROOT )?%ragged-dot[\w.-]* = s32\[(\d+),(\d+)\]",
                       text, re.M)
    want = sorted((r, n) for r in rungs for n in (width, width, hidden))
    assert sorted((int(r), int(n)) for r, n in named) == want
    # the branches read the int8 stacks where they lie
    assert not re.search(r"s8\[(192|8,24),\d+,\d+\]\S* copy\(", text)
    assert len(re.findall(r" conditional\(", text)) == 1


def test_ragged_dispatch_compiles_head_parallel_on_four_chips(topo):
    """`chip_smoke.py --chips 4`'s mixed step: the dispatcher's shard_map
    over a (data=1, model=4) mesh hands each chip 7 query / 1 KV head."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from dynamo_tpu.ops import attention as att

    mesh = Mesh(np.array(topo.devices).reshape(1, 4), ("data", "model"))

    def arg(shape, dtype, spec):
        return jax.ShapeDtypeStruct(shape, dtype,
                                    sharding=NamedSharding(mesh, spec))

    pool = arg((POOL_PAGES, PAGE, 4 * HEAD_DIM), jnp.bfloat16,
               P(None, None, "model"))

    def step(q, kp, vp, tables, ctx, pages, start):
        with att.attention_context("pallas", mesh):
            return att.ragged_mixed_attention(
                q, kp, vp, tables, ctx, pages, start, page_size=PAGE,
                num_kv_heads=4, num_decode=SLOTS)

    compiled = jax.jit(step).lower(
        arg((SLOTS + CHUNK, 28, HEAD_DIM), jnp.bfloat16,
            P(None, "model", None)), pool, pool,
        arg((SLOTS, 128), jnp.int32, P()), arg((SLOTS,), jnp.int32, P()),
        arg((143,), jnp.int32, P()), arg((), jnp.int32, P())).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    assert "all-gather" not in text and "all-reduce" not in text


def test_decode_dispatch_compiles_head_parallel_on_four_chips(topo):
    """`chip_smoke.py --chips 4`'s decode window: the dispatcher's shard_map
    hands each chip 7 query / 1 KV head and the kernel's lens (context 0
    for an empty slot), with no collective."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from dynamo_tpu.ops import attention as att

    mesh = Mesh(np.array(topo.devices).reshape(1, 4), ("data", "model"))

    def arg(shape, dtype, spec):
        return jax.ShapeDtypeStruct(shape, dtype,
                                    sharding=NamedSharding(mesh, spec))

    pool = arg((POOL_PAGES, PAGE, 4 * HEAD_DIM), jnp.bfloat16,
               P(None, None, "model"))

    def step(q, kp, vp, tables, ctx):
        with att.attention_context("pallas", mesh):
            return att.paged_attention_decode(
                q, kp, vp, tables, ctx, page_size=PAGE, num_kv_heads=4,
                kernel_lens=jnp.where(tables[:, 0] > 0, ctx, 0))

    compiled = jax.jit(step).lower(
        arg((SLOTS, 28, HEAD_DIM), jnp.bfloat16, P(None, "model", None)),
        pool, pool, arg((SLOTS, 128), jnp.int32, P()),
        arg((SLOTS,), jnp.int32, P())).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    assert "all-gather" not in text and "all-reduce" not in text


@pytest.mark.parametrize("kind, rows", [("decode", 8), ("decode", 64),
                                        ("chunk", 32), ("chunk_tail", 32)])
def test_sparse_selection_compiles_for_v5e_at_cell_shapes(one_chip, kind,
                                                          rows):
    """The DeepSeek-V3.2 cell's selection (plain XLA: ops/attention.dsa_*)
    in decode, at a rung of 8 live slots and at the whole 64-slot batch,
    and in a 256-query chunk (blocks of 32), 128 heads on 640-lane rows, a
    2,048-page table of a 9-layer pool, 2,048 rows kept: compiles for a
    described v5e; the selection is ONE sort over the [rows, 32768] float32
    scores with ONE int32 payload (the trace finds it by that shape:
    layer_metrics/dsa_select_busy_pct.docqa.json), which carries the
    physical rows, so nothing is looked up in the page table one scalar at
    a time (PR 35: no gather with an integer result; `take_along_axis`
    was 7% of the cell's device time); its scratch stays under a tenth of
    the chip (the index keys and float32 products of every slot's whole
    table are the intermediates a kernel of its own would not need:
    ROADMAP, Reach).
    `chunk_tail` is the chunk as the cell runs it (PR 38): a table of 2,063
    entries, the bucket's 2,048 pages and the 15 trailing trash slots of a
    256-token chunk, handed to the op with the bucket's pages as the
    selection's extent: its sort is over [32, 32768] all the same (the
    TPU works a 33,008-wide sort as 65,536), and no instruction makes
    anything 33,008 or 2,063 wide: only the table's parameter is."""
    import re

    import jax
    import jax.numpy as jnp

    from dynamo_tpu.ops import attention as att

    def arg(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    layer_pages, ps, pmax = 8192, 16, 2048
    n = rows if kind == "decode" else 256
    wide = pmax + att.chunk_table_tail(n, ps)  # 2,063
    op, table, last = {
        "decode": (att.dsa_decode_attention, (n, pmax), (n,)),
        "chunk": (att.dsa_chunk_attention, (pmax,), ()),
        "chunk_tail": (functools.partial(att.dsa_chunk_attention,
                                         key_pages=pmax), (wide,), ()),
    }[kind]
    compiled = jax.jit(
        lambda off, *a: op(*a, page_size=ps, topk=2048, page_off=off,
                           layer_pages=layer_pages)
    ).lower(
        arg((), jnp.int32), arg((n, 128, 640), jnp.bfloat16),
        arg((n, 64, 128), jnp.bfloat16), arg((n, 64), jnp.float32),
        arg((9 * layer_pages, ps, 640), jnp.bfloat16),
        arg((9 * layer_pages, ps, 128), jnp.bfloat16),
        arg(table, jnp.int32), arg(last, jnp.int32)).compile()
    text = compiled.as_text()
    sorts = re.findall(r"^.* sort\(.*$", text, re.M)
    assert len(sorts) == 1
    assert re.search(rf"= \(f32\[{rows},32768\]\S*, s32\[{rows},32768\]\S*\) "
                     r"sort\(", sorts[0]), sorts[0][:300]
    assert not re.search(r"= s32\[[\d,]*\]\S* gather\(", text)
    assert "take_along_axis" not in text
    assert compiled.memory_analysis().temp_size_in_bytes < 1.6e9
    if kind == "chunk_tail":
        assert (wide, wide * ps) == (2063, 33008)
        made = re.findall(r"= \(?\w+\[(?:\d+,)*(?:2063|33008)[,\]]\S* "
                          r"(?:\S+ )*?([\w-]+)\(", text)
        assert set(made) == {"parameter"}, made


# Laguna's cut (benchmarks/chip/configs/laguna-s-2.1-w8a8-1chip): layers of
# two kinds, 48 / 72 query heads over 8 KV heads, the sliding layers under a
# static window of 512 over rings of 49 pages
LAGUNA_SLOTS, LAGUNA_RING, LAGUNA_WINDOW = 64, 49, 512


@pytest.mark.parametrize("heads,width,window", [(48, 2048, 0),
                                                (72, LAGUNA_RING,
                                                 LAGUNA_WINDOW)],
                         ids=["full_48q8kv", "window_72q8kv_ring49"])
@pytest.mark.parametrize("decode", [LAGUNA_SLOTS, 0],
                         ids=["mixed_64_slots", "chunk_alone"])
def test_windowed_kernels_compile_for_v5e_at_laguna_shapes(one_chip, heads,
                                                           width, window,
                                                           decode):
    """The ragged kernel at 6 and 9 query heads a KV head, with 64 decode
    rows and with none (a windowed chunk alone), and the decode kernel at
    the same, each under the sliding layers' static window where it has
    one: Mosaic takes them, and their outputs have the shapes the
    benchmark's trace readers find them by
    (layer_metrics/*_attn_*.longmix.json)."""
    import jax
    import jax.numpy as jnp

    from dynamo_tpu.ops import pallas_attention as pa
    from dynamo_tpu.ops import ragged_attention as ra

    def arg(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    pool = arg((POOL_PAGES, PAGE, 8 * HEAD_DIM), jnp.bfloat16)
    text = jax.jit(functools.partial(
        ra.ragged_paged_attention, page_size=PAGE, num_kv_heads=8,
        num_decode=decode, window=window)).lower(
        arg((decode + CHUNK, heads, HEAD_DIM), jnp.bfloat16), pool, pool,
        arg((decode + 1, width), jnp.int32), arg((decode + 1,), jnp.int32),
        arg((decode + 1,), jnp.int32)).compile().as_text()
    assert f"bf16[{decode + CHUNK // 8},8,{heads},{HEAD_DIM}]" in text
    if not decode:
        return
    text = jax.jit(functools.partial(
        pa.paged_attention_decode, page_size=PAGE, num_kv_heads=8,
        window=window)).lower(
        arg((decode, heads, HEAD_DIM), jnp.bfloat16), pool, pool,
        arg((decode, width), jnp.int32), arg((decode,), jnp.int32)
    ).compile().as_text()
    assert text.count("tpu_custom_call") == 1
    assert f"bf16[{decode},{heads},{HEAD_DIM}]" in text


def test_laguna_decode_step_compiles_for_v5e_under_its_scope_names(one_chip):
    """The cut model's whole decode step at the cell's sizes (w8a8, 64
    slots, a 2,048-page table beside a ring of 49, both pools), for a
    described v5e: no op leaves the kernels (no counted fallback), the
    kernels carry the names of their attention kind, the gate, the router,
    the grouped matmuls and the shared expert are named in the HLO, and
    everything beside the arguments stays under a tenth of a gigabyte."""
    import re

    import jax
    import jax.numpy as jnp

    from dynamo_tpu.engine.kv_cache import KVCacheSpec
    from dynamo_tpu.models import llama, quant
    from dynamo_tpu.models.config import ModelConfig
    from dynamo_tpu.ops import attention as att

    def arg(shape, dtype):
        return jax.ShapeDtypeStruct(tuple(shape), dtype, sharding=one_chip)

    cfg = ModelConfig.from_model_name(os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        "benchmarks/chip/configs/laguna-s-2.1-w8a8-1chip"))
    spec = KVCacheSpec.from_model(cfg, 8192, PAGE,
                                  window_slots=LAGUNA_SLOTS + 1,
                                  window_ahead=CHUNK)
    assert spec.ring_pages == LAGUNA_RING
    params = {}
    for name, (shape, kind, _) in llama.param_specs(cfg).items():
        axes = quant.quant_axes(name)
        if axes and kind == "normal":
            params[name] = quant.QTensorA8(
                arg(shape, jnp.int8),
                arg([1 if i in axes else s for i, s in enumerate(shape)],
                    jnp.float32))
        else:
            params[name] = arg(shape, jnp.bfloat16)
    pools = llama.ByKind(arg(spec.shape, jnp.bfloat16),
                         arg(spec.window_shape, jnp.bfloat16))
    b = LAGUNA_SLOTS
    before = dict(att.pallas_fallback_counts())
    with att.attention_context("pallas", None, 1):
        compiled = jax.jit(functools.partial(
            llama.decode_step, cfg, page_size=PAGE)).lower(
            params, arg((b,), jnp.int32), arg((b,), jnp.int32),
            llama.ByKind(arg((b, 2048), jnp.int32),
                         arg((b, LAGUNA_RING), jnp.int32)),
            arg((b,), jnp.int32), pools, pools).compile()
    assert dict(att.pallas_fallback_counts()) == before
    text = compiled.as_text()
    kernels = re.findall(
        r"^\s*%(attn_\w+?)[\d.]* = bf16\[64,(\d+),128\]\S* custom-call\(",
        text, re.M)
    # the dense layer's and the full layer's bodies, and ONE body for the
    # period's run of three sliding layers (scanned since PR 48)
    assert sorted(kernels) == [("attn_full", "48")] * 2 + [
        ("attn_window", "72")]
    for scope in ("attn_gate", "moe_router", "moe_experts",
                  "moe_shared_expert"):
        assert scope in text, scope
    assert compiled.memory_analysis().temp_size_in_bytes < 1e8


def test_prefill_kernel_compiles_for_v5e_at_nine_heads_a_kv_head(one_chip):
    """The whole-prompt flash kernel at the sliding layers' 72 / 8 heads:
    its query block is a power of two for every group (9 heads a KV head
    asked for 113 rows, which Mosaic cannot tile: the first chip call of
    PR 36 died of it in warm-up)."""
    import jax
    import jax.numpy as jnp

    from dynamo_tpu.ops import pallas_attention as pa

    def arg(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    for s in (16, 256):
        text = jax.jit(pa.prefill_attention).lower(
            arg((s, 72, HEAD_DIM), jnp.bfloat16),
            arg((s, 8, HEAD_DIM), jnp.bfloat16),
            arg((s, 8, HEAD_DIM), jnp.bfloat16),
            arg((), jnp.int32)).compile().as_text()
        assert "tpu_custom_call" in text


def _hybrid_window(cfg):
    """A hybrid model's fused decode window as the engine runs it
    (engine.make_decode_window): the step in a loop whose trip count is
    the last operand (1 .. 16), each step's tokens written into their row
    of a preallocated [16, B] result, the pools and states its carry, the
    live slots' list built once for all its steps."""
    import jax
    import jax.numpy as jnp

    from dynamo_tpu.models import llama

    def window(params, tokens, positions, tables, lens, kp, vp, steps):
        slots = llama.live_state_slots(cfg, tables)

        def body(i, loop):
            (toks, pos, ctx, kp, vp), ys = loop
            out = llama.decode_step(
                cfg, params, toks, pos, tables, ctx, kp, vp,
                page_size=PAGE, state_slots=slots)
            nxt = jnp.argmax(out.logits, axis=-1).astype(jnp.int32)
            return ((nxt, pos + 1, ctx + 1, out.k_pages, out.v_pages),
                    ys.at[i].set(nxt))

        return jax.lax.fori_loop(
            0, steps, body,
            ((tokens, positions, lens, kp, vp),
             jnp.zeros((16,) + tokens.shape, jnp.int32)))
    return window


def _loops_bounded_by_an_operand(text):
    """The `while` operations of a compiled program whose condition holds
    no constant, so that the counter is compared with a value of the
    loop's own tuple: a fused window's loop, whose trip count is an
    operand, and no other loop of a step program (a layer scan's condition
    compares with `constant(<layers>)`)."""
    import re

    found = []
    for name in re.findall(r" while\(.*?condition=%([\w.\-]+)", text):
        cond = text.split("\n%" + name + " (", 1)[1].split("\n}", 1)[0]
        if "constant(" not in cond:
            found.append(name)
    return found


# NVIDIA-Nemotron-3-Nano's cut (PR 42): 64 slots = 64 state slots, a decode
# table of 6,144 tokens, the one chunked-prompt table width the engine keeps
NEMOTRON_SLOTS, NEMOTRON_TABLE, NEMOTRON_CHUNK_TABLE = 64, 384, 399
NEMOTRON_SCOPES = ("ssm_in_proj", "ssm_conv", "ssm_scan", "ssm_gate_norm",
                   "ssm_out_proj", "attn_full", "moe_router", "moe_experts",
                   "moe_shared_expert")


@pytest.mark.parametrize("program", ["decode", "mixed", "window"])
def test_nemotron_step_compiles_for_v5e_under_its_scope_names(one_chip,
                                                              program):
    """The cut model's whole decode step, mixed step and a fused window of
    up to 16 steps (the step in a loop whose trip count is an operand, the
    pools and states its donated carry) at
    the cell's sizes (w8a8, 64 slots and their states, a 256-token chunk),
    for a described v5e: no op leaves the kernels (16 query heads over each
    of 2 KV heads, 256 lanes a row, no rotary; the state update over the
    live slots: no counted fallback), every span the benchmark reads is
    named in the HLO, each Mamba-2 layer's update is the kernel's custom
    call under `ssm_scan` with the WHOLE state array f32[64,64,64,128] its
    operand and its result (what `ssm_decode_update_roofline.reason` and
    `ssm_scan_busy_pct.reason` find it by), no state-sized array is copied,
    and everything beside the arguments stays under 0.06 GB (0.1 for the
    mixed step), less than half a state (the experts' stack is NOT copied
    either: ModelConfig.expert_dims_stored says what that took)."""
    import re

    import jax
    import jax.numpy as jnp

    from dynamo_tpu.engine.kv_cache import KVCacheSpec
    from dynamo_tpu.models import llama, quant
    from dynamo_tpu.models.config import ModelConfig
    from dynamo_tpu.ops import attention as att

    def arg(shape, dtype):
        return jax.ShapeDtypeStruct(tuple(shape), dtype, sharding=one_chip)

    def i32(*shape):
        return arg(shape, jnp.int32)

    cfg = ModelConfig.from_model_name(os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        "benchmarks/chip/configs/nemotron3-nano-w8a8-1chip"))
    b = NEMOTRON_SLOTS
    spec = KVCacheSpec.from_model(cfg, 8192, PAGE, state_slots=b)
    params = {}
    for name, (shape, kind, _) in llama.param_specs(cfg).items():
        axes = quant.quant_axes(name)
        if axes and kind == "normal":
            params[name] = quant.QTensorA8(
                arg(shape, jnp.int8),
                arg([1 if i in axes else s for i, s in enumerate(shape)],
                    jnp.float32))
        else:
            params[name] = arg(shape, jnp.float32 if (
                kind in llama.SSM_INITS or name == "router_bias")
                else jnp.bfloat16)
    assert params["moe_w_up"].q.shape == (4, 128, 3072, 2048)
    assert params["moe_w_down"].q.shape == (4, 128, 2048, 3072)
    kp = llama.StatePools(arg(spec.shape, jnp.bfloat16), tuple(
        arg((b,) + spec.ssm_shape, jnp.float32) for _ in range(4)))
    vp = llama.StatePools(arg(spec.v_shape, jnp.bfloat16), tuple(
        arg((b,) + spec.conv_shape, jnp.bfloat16) for _ in range(4)))
    before = dict(att.pallas_fallback_counts())
    with att.attention_context("pallas", None, 1):
        if program == "decode":
            compiled = jax.jit(functools.partial(
                llama.decode_step, cfg, page_size=PAGE),
                donate_argnums=(5, 6)).lower(
                params, i32(b), i32(b), i32(b, NEMOTRON_TABLE), i32(b), kp,
                vp).compile()
        elif program == "window":
            compiled = jax.jit(_hybrid_window(cfg),
                               donate_argnums=(5, 6)).lower(
                params, i32(b), i32(b), i32(b, NEMOTRON_TABLE), i32(b), kp,
                vp, i32()).compile()
        else:
            compiled = jax.jit(functools.partial(
                llama.mixed_step, cfg, page_size=PAGE),
                donate_argnums=(9, 10)).lower(
                params, i32(b), i32(b), i32(b, NEMOTRON_TABLE), i32(b),
                i32(CHUNK), i32(), i32(),
                llama.SlotPages(i32(NEMOTRON_CHUNK_TABLE), i32()), kp,
                vp).compile()
    assert dict(att.pallas_fallback_counts()) == before
    text = compiled.as_text()
    for scope in NEMOTRON_SCOPES:
        assert scope in text, scope
    assert not re.search(r"f32\[64,64,64,128\]\S* copy\(", text)
    # a state is 0.134 GB. The mixed step's 0.072 GB are the experts'
    # f32[1920,3072] intermediates and the compiler's prefetch copies of
    # rows (buffer assignment read at PR 43): no state among them
    assert compiled.memory_analysis().temp_size_in_bytes < (
        0.1e9 if program == "mixed" else 0.06e9)
    # the window's loop alone is bounded by an operand (its trip count)
    assert len(_loops_bounded_by_an_operand(text)) == (program == "window")
    updates = [line for line in text.splitlines()
               if "tpu_custom_call" in line and "ssm_update_live" in line]
    assert len(updates) == 4  # one a Mamba-2 layer, in a window's body too
    for line in updates:
        assert "ssm_scan" in line  # the scope, in the op's metadata
        result, operands = line.split(" custom-call(", 1)
        assert "f32[64,64,64,128]" in result
        assert "f32[64,64,64,128]" in operands.split("metadata=")[0]
        assert "output_to_operand_aliasing={{1}: (8, {})}" in line


# Falcon-H1-34B's cut (PR 44): 64 slots = 64 state slots in EVERY layer, a
# decode table of 6,144 tokens, the one chunked-prompt table width
FALCON_SLOTS, FALCON_TABLE, FALCON_CHUNK_TABLE = 64, 384, 399
FALCON_PAGES, FALCON_LAYERS = 6144, 10
FALCON_SCOPES = ("ssm_in_proj", "ssm_conv", "ssm_scan", "ssm_gate_norm",
                 "ssm_out_proj", "attn_full", "mixer_attn", "mixer_ssm",
                 "mixer_sum", "mlp_dense")


def _falcon_cut(one_chip):
    """(cfg, abstract w8a8 params, k_pages, v_pages) of the cut at the
    cell's sizes, for a described v5e."""
    import jax
    import jax.numpy as jnp

    from dynamo_tpu.engine.kv_cache import KVCacheSpec
    from dynamo_tpu.models import llama, quant
    from dynamo_tpu.models.config import ModelConfig

    def arg(shape, dtype):
        return jax.ShapeDtypeStruct(tuple(shape), dtype, sharding=one_chip)

    cfg = ModelConfig.from_model_name(os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        "benchmarks/chip/configs/falcon-h1-34b-w8a8-1chip"))
    assert cfg.num_layers == FALCON_LAYERS
    b = FALCON_SLOTS
    spec = KVCacheSpec.from_model(cfg, FALCON_PAGES, PAGE, state_slots=b)
    params = {}
    for name, (shape, kind, _) in llama.param_specs(cfg).items():
        axes = quant.quant_axes(name)
        if axes and kind == "normal":
            params[name] = quant.QTensorA8(
                arg(shape, jnp.int8),
                arg([1 if i in axes else s for i, s in enumerate(shape)],
                    jnp.float32))
        else:
            params[name] = arg(shape, jnp.float32 if kind in llama.SSM_INITS
                               else jnp.bfloat16)
    lead = (spec.state_layers, b)
    kp = llama.StatePools(arg(spec.shape, jnp.bfloat16),
                          (arg(lead + spec.ssm_shape, jnp.float32),))
    vp = llama.StatePools(arg(spec.v_shape, jnp.bfloat16),
                          (arg(lead + spec.conv_shape, jnp.bfloat16),))
    return cfg, params, kp, vp, arg


@pytest.mark.parametrize("program", ["decode", "mixed", "window", "prefill"])
def test_falcon_h1_step_compiles_for_v5e_under_its_scope_names(one_chip,
                                                               program):
    """The cut model's decode step, mixed step, a 16-step window and a
    whole-prompt prefill at the cell's sizes (w8a8, 64 slots, their states
    in ALL ten layers as one pool f32[640,32,128,256], a 256-token chunk),
    for a described v5e: the layers are ONE scan; no op leaves the kernels
    (5 query heads a KV head, a rotary; the state update over the live
    slots at a head's 128 x 256 state: no counted fallback, no
    `state_tiling` demotion); every span the benchmark reads and the three
    branch scopes are named in the HLO; the update is ONE custom call (the
    scan's body) named `ssm_update_live` under `ssm_scan`, the whole pool
    its operand and its result; the state pool is never copied, nor the
    stacks of the MLP (3.3 GB), W_q, W_o and W_out. What IS copied, read
    at PR 44: the conv rows' pool bf16[10,64,3,5120] (19.7 MB) into the
    layer scan's layout and back, once a program; and in the 16-step
    window alone (a scan in a scan), once a window ahead of its steps,
    W_in's stack s8[10,5120,9248] (473 MB: the TPU lays an int8 array
    whose minor extent is no multiple of 128 out transposed, and the
    nested scan slices it row-major) and W_k / W_v (26 MB each): 1.2 ms a
    window, 0.07 ms a token, and 0.5 GB beside the arguments (PERF.md
    section 7). Beside its arguments (11.70 GB) a program holds under 0.05
    GB (decode, mixed), 0.6 (window), 0.9 (a whole prompt's float
    matmuls)."""
    import re

    import jax
    import jax.numpy as jnp

    from dynamo_tpu.models import llama
    from dynamo_tpu.ops import attention as att

    cfg, params, kp, vp, arg = _falcon_cut(one_chip)
    b = FALCON_SLOTS

    def i32(*shape):
        return arg(shape, jnp.int32)

    before = dict(att.pallas_fallback_counts())
    with att.attention_context("pallas", None, 1):
        if program == "decode":
            compiled = jax.jit(functools.partial(
                llama.decode_step, cfg, page_size=PAGE),
                donate_argnums=(5, 6)).lower(
                params, i32(b), i32(b), i32(b, FALCON_TABLE), i32(b), kp,
                vp).compile()
        elif program == "window":
            compiled = jax.jit(_hybrid_window(cfg),
                               donate_argnums=(5, 6)).lower(
                params, i32(b), i32(b), i32(b, FALCON_TABLE), i32(b), kp,
                vp, i32()).compile()
        elif program == "mixed":
            compiled = jax.jit(functools.partial(
                llama.mixed_step, cfg, page_size=PAGE),
                donate_argnums=(9, 10)).lower(
                params, i32(b), i32(b), i32(b, FALCON_TABLE), i32(b),
                i32(CHUNK), i32(), i32(),
                llama.SlotPages(i32(FALCON_CHUNK_TABLE), i32()), kp,
                vp).compile()
        else:
            compiled = jax.jit(functools.partial(
                llama.prefill, cfg, page_size=PAGE),
                donate_argnums=(3, 4)).lower(
                params, i32(128), i32(), kp, vp,
                llama.SlotPages(i32(8), i32())).compile()
    assert dict(att.pallas_fallback_counts()) == before
    text = compiled.as_text()
    for scope in FALCON_SCOPES:
        assert scope in text, scope
    pool = "f32[640,32,128,256]"
    assert not re.search(r"f32\[(640|10,64),32,128,256\]\S* copy\(", text)
    assert not re.search(
        r"s8\[10,(5120,21504|21504,5120|5120,20,128|20,128,5120|4096,5120)"
        r"\]\S* copy\(", text)
    if program != "window":
        assert not re.search(r"s8\[10,\S* copy\(", text)
    mem = compiled.memory_analysis()
    assert 11.6e9 < mem.argument_size_in_bytes < 11.8e9
    assert mem.temp_size_in_bytes < {
        "window": 0.6e9, "prefill": 0.9e9}.get(program, 0.05e9)
    # the window's loop is bounded by its trip count, an operand (PR 55):
    # what it copies is the list above, all of it in the entry computation
    # (once a window whatever its length), and it holds 65,536 bytes more
    # than the 16-step scan it replaced (558,939,136), the [16, B] result
    assert len(_loops_bounded_by_an_operand(text)) == (program == "window")
    if program == "window":
        assert mem.temp_size_in_bytes < 0.56e9
        body = text.split("\nENTRY ", 1)[0]
        assert not re.search(r"s8\[10,\S* copy\(", body)
    if program == "prefill":
        return  # a prompt alone runs the chunked scan, not the update
    updates = [line for line in text.splitlines()
               if "tpu_custom_call" in line and "ssm_update_live" in line]
    assert len(updates) == 1  # the layer scan's body
    (line,) = updates
    assert "ssm_scan" in line and "mixer_ssm" in line
    result, operands = line.split(" custom-call(", 1)
    assert pool in result
    assert pool in operands.split("metadata=")[0]
    assert "output_to_operand_aliasing={{1}: (9, {})}" in line
    attn = [l for l in text.splitlines()
            if "tpu_custom_call" in l and "attn_full" in l]
    assert attn and all("mixer_attn" in l for l in attn)


# MiMo-V2.5's cut (PR 48): 64 slots, a 2,048-page table beside a ring of 25
# pages, 24,576 full pages; keys 192 lanes a head, values 128; 4 KV heads on
# the full layers (16 query heads each), 8 on the sliding ones
MIMO_SLOTS, MIMO_RING, MIMO_TABLE, MIMO_PAGES = 64, 25, 2048, 24576
MIMO_SCOPES = ("attn_full", "attn_window", "attn_qk_rope", "moe_router",
               "moe_experts")


def _mimo_cut(one_chip):
    """(cfg, abstract w8a8 params, k_pages, v_pages, arg) of the cut at the
    cell's sizes, for a described v5e."""
    import jax
    import jax.numpy as jnp

    from dynamo_tpu.engine.kv_cache import KVCacheSpec
    from dynamo_tpu.models import llama, quant
    from dynamo_tpu.models.config import ModelConfig

    def arg(shape, dtype):
        return jax.ShapeDtypeStruct(tuple(shape), dtype, sharding=one_chip)

    cfg = ModelConfig.from_model_name(os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        "benchmarks/chip/configs/mimo-v2.5-w8a8-ep16-1chip"))
    spec = KVCacheSpec.from_model(cfg, MIMO_PAGES, PAGE,
                                  window_slots=MIMO_SLOTS + 1,
                                  window_ahead=CHUNK)
    assert spec.ring_pages == MIMO_RING
    params = {}
    for name, (shape, kind, _) in llama.param_specs(cfg).items():
        axes = quant.quant_axes(name)
        if axes and kind == "normal":
            params[name] = quant.QTensorA8(
                arg(shape, jnp.int8),
                arg([1 if i in axes else s for i, s in enumerate(shape)],
                    jnp.float32))
        else:
            params[name] = arg(shape, jnp.float32 if kind == "sink"
                               or name == "router_bias" else jnp.bfloat16)
    kp = llama.ByKind(arg(spec.shape, jnp.bfloat16),
                      arg(spec.window_shape, jnp.bfloat16))
    vp = llama.ByKind(arg(spec.v_shape, jnp.bfloat16),
                      arg(spec.window_v_shape, jnp.bfloat16))
    return cfg, params, kp, vp, arg


@pytest.mark.parametrize("program", ["decode", "mixed"])
def test_mimo_step_compiles_for_v5e_under_its_scope_names(one_chip, program):
    """The cut model's decode step and mixed step at the cell's sizes (w8a8,
    64 slots, a 2,048-page table beside a ring of 25, a pool a kind whose K
    rows are 768 / 1,536 lanes and V rows 512 / 1,024, a 256-token chunk),
    for a described v5e: no op leaves the kernels (K 192 / V 128 lanes a
    head, 16 and 8 query heads a KV head: no counted fallback); a program
    holds THREE layer bodies (the dense layer, the scanned run of sliding
    layers, the full layer) whatever the depth; the sliding kind's kernel
    takes the sink as an f32[rows, 1] operand and the full kind's takes
    none; the decode kernels carry their kind's scope as the instruction's
    name (the ragged kernel, a jit of its own, is named after itself in
    both kinds: the benchmark tells them apart by the page table's width,
    and its patterns are held to that here); the partial rotary, the router
    and the grouped matmuls are named in the HLO, and `attn_sink` is not
    (inside a kernel the sink has no HLO of its own); no pool and no weight
    is copied but W_v (the sliding layers' stack s8[10,4096,8,128], 42 MB,
    and a full layer's 2 MB, into the layout their matmul takes: read at PR
    48, PERF.md section 7); beside its arguments (10.79 GB) a program holds
    under 0.15 GB."""
    import re

    import jax
    import jax.numpy as jnp

    from dynamo_tpu.models import llama
    from dynamo_tpu.ops import attention as att

    cfg, params, kp, vp, arg = _mimo_cut(one_chip)
    b = MIMO_SLOTS

    def i32(*shape):
        return arg(shape, jnp.int32)

    tables = llama.ByKind(i32(b, MIMO_TABLE), i32(b, MIMO_RING))
    before = dict(att.pallas_fallback_counts())
    with att.attention_context("pallas", None, 1):
        if program == "decode":
            compiled = jax.jit(functools.partial(
                llama.decode_step, cfg, page_size=PAGE),
                donate_argnums=(5, 6)).lower(
                params, i32(b), i32(b), tables, i32(b), kp, vp).compile()
        else:
            compiled = jax.jit(functools.partial(
                llama.mixed_step, cfg, page_size=PAGE),
                donate_argnums=(9, 10)).lower(
                params, i32(b), i32(b), tables, i32(b), i32(CHUNK), i32(),
                i32(), llama.ByKind(i32(MIMO_TABLE + 15), i32(MIMO_RING)),
                kp, vp).compile()
    assert dict(att.pallas_fallback_counts()) == before
    text = compiled.as_text()
    for scope in MIMO_SCOPES:
        assert scope in text, scope
    assert "attn_sink" not in text
    out = ("64,64,128" if program == "decode"
           else f"{b + CHUNK // 8},8,64,128")
    calls = [ln.strip() for ln in text.splitlines()
             if "tpu_custom_call" in ln and f" = bf16[{out}]" in ln]
    assert len(calls) == 3  # dense layer, the sliding run's body, full layer
    window = [ln for ln in calls if f",{MIMO_RING}]{{1,0}}" in ln]
    full = [ln for ln in calls if ln not in window]
    assert len(window) == 1 and len(full) == 2
    rows = 64 if program == "decode" else 8 * 64
    for ln in window:
        assert "bf16[16260,16,1536]" in ln and "bf16[16260,16,1024]" in ln
        assert f"f32[{rows},1]" in ln  # the sink, an operand
    for ln in full:
        assert "bf16[73728,16,768]" in ln and "bf16[73728,16,512]" in ln
        assert "f32[" not in ln.split("frontend_attributes")[0]
    if program == "decode":
        assert window[0].startswith("%attn_window")
        assert all(ln.startswith("%attn_full") for ln in full)
    # the benchmark's patterns, on the trace's spelling of an operation (its
    # first operand's shape follows `custom-call(`)
    def as_traced(ln):
        first = re.search(r"operand_layout_constraints=\{(s32\[[\d,]+\])",
                          ln).group(1)
        return ln.replace("custom-call(", f"custom-call({first}{{1,0}} ", 1)

    win_pat, full_pat, roof = _layer_metric_patterns(
        "window_attn_busy_pct.longreason", "full_attn_busy_pct.longreason",
        f"gqa_{program}_attn_roofline.longreason")
    for ln in window:
        assert re.search(win_pat, as_traced(ln))
        assert not re.search(full_pat, as_traced(ln))
    for ln in full:
        assert re.search(full_pat, as_traced(ln))
        assert not re.search(win_pat, as_traced(ln))
    assert all(re.search(roof, as_traced(ln)) for ln in calls)
    # no pool is copied, and no weight but W_v (the sliding layers' stack,
    # 42 MB, and a full layer's 2 MB)
    assert not re.search(r"bf16\[(3,24576|73728|10,1626|16260),16,\d+\]\S* "
                         r"copy\(", text)
    copied = set(re.findall(r"= (s8\[[\d,]+\])\S* copy\(", text))
    assert all(re.fullmatch(r"s8\[(\d+,)?4096,[48],128\]", c)
               or eval(c[3:-1].replace(",", "*")) < 1 << 20  # activations
               for c in copied), copied
    assert text.count(" while(") == 2  # the periods' scan, the run's inside
    mem = compiled.memory_analysis()
    assert 10.7e9 < mem.argument_size_in_bytes < 10.9e9
    assert mem.temp_size_in_bytes < 0.15e9


@pytest.mark.parametrize("s,kv_heads,sink", [(128, 8, True), (256, 4, False)],
                         ids=["sliding_in_window", "full_bucket_256"])
def test_flash_kernel_compiles_for_v5e_at_wider_keys(one_chip, s, kv_heads,
                                                     sink):
    """The whole-prompt flash kernel at K 192 / V 128 lanes a head, with
    the sink a query head (a sliding layer's bucket inside the window) and
    without (a full layer's): Mosaic takes a 192-lane block."""
    import jax
    import jax.numpy as jnp

    from dynamo_tpu.ops import pallas_attention as pa

    def arg(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    kw = {"sink": arg((64,), jnp.float32)} if sink else {}
    text = jax.jit(pa.prefill_attention).lower(
        arg((s, 64, 192), jnp.bfloat16), arg((s, kv_heads, 192), jnp.bfloat16),
        arg((s, kv_heads, 128), jnp.bfloat16), arg((), jnp.int32),
        **kw).compile().as_text()
    assert "tpu_custom_call" in text
    assert f"bf16[{kv_heads},{64 // kv_heads},{s},128]" in text


# LFM2-8B-A1B as published (PR 52): 64 slots = 64 state slots of conv rows
# alone, a decode table of 4,096 tokens, the one chunked-prompt table width
LFM2_SLOTS, LFM2_TABLE, LFM2_CHUNK_TABLE = 64, 256, 271
LFM2_SCOPES = ("conv_in_proj", "conv_gate_taps", "conv_out_proj", "attn_full",
               "attn_qk_norm", "attn_qk_rope", "mlp_dense", "moe_router",
               "moe_experts")


@pytest.mark.parametrize("program", ["decode", "mixed", "window", "prefill",
                                     "chunk"])
def test_lfm2_step_compiles_for_v5e_under_its_scope_names(one_chip, program):
    """The WHOLE published model's decode step, mixed step, 16-step window,
    whole-prompt prefill and prompt chunk at the cell's sizes (w8a8, all 24 layers, 32
    experts a layer, 64 slots and their conv rows, a 256-token chunk), for a
    described v5e: no op leaves the kernels at 64-lane heads (4 query heads
    a KV head, a 512-lane row: no counted fallback), every span the
    benchmark and the docs name is in the HLO, the attention kernels are
    found by the patterns of the cell's roofline metrics, the program holds
    TWO layer scans and ONE conditional over the operator's kind whatever
    the depth, the experts' matmuls are the grouped-matmul kernel of our
    own, no pool, no conv-row array and no weight stack is copied
    (everything beside the arguments stays under 0.06 GB: the pool is 0.8
    GB, the smallest stack 25 MB), and the arguments are the memory the
    configuration file reckons."""
    import re

    import jax
    import jax.numpy as jnp

    from dynamo_tpu.engine.kv_cache import KVCacheSpec
    from dynamo_tpu.models import llama, quant
    from dynamo_tpu.models.config import ModelConfig
    from dynamo_tpu.ops import attention as att

    def arg(shape, dtype):
        return jax.ShapeDtypeStruct(tuple(shape), dtype, sharding=one_chip)

    def i32(*shape):
        return arg(shape, jnp.int32)

    cfg = ModelConfig.from_model_name(os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        "benchmarks/chip/configs/lfm2-8b-a1b-w8a8-1chip"))
    b = LFM2_SLOTS
    spec = KVCacheSpec.from_model(cfg, 8192, PAGE, state_slots=b)
    assert spec.shape == (6, 8192, PAGE, 512) and spec.ssm_shape == ()
    assert spec.bytes_per_slot() == 147456
    params = {}
    for name, (shape, kind, _) in llama.param_specs(cfg).items():
        axes = quant.quant_axes(name)
        if axes and kind == "normal":
            params[name] = quant.QTensorA8(
                arg(shape, jnp.int8),
                arg([1 if i in axes else s for i, s in enumerate(shape)],
                    jnp.float32))
        else:
            params[name] = arg(shape, jnp.float32 if name == "router_bias"
                               else jnp.bfloat16)
    kp = llama.StatePools(arg(spec.shape, jnp.bfloat16), ())
    vp = llama.StatePools(arg(spec.v_shape, jnp.bfloat16),
                          (arg((18, b) + spec.conv_shape, jnp.bfloat16),))
    before = dict(att.pallas_fallback_counts())
    with att.attention_context("pallas", None, 1):
        if program == "decode":
            compiled = jax.jit(functools.partial(
                llama.decode_step, cfg, page_size=PAGE),
                donate_argnums=(5, 6)).lower(
                params, i32(b), i32(b), i32(b, LFM2_TABLE), i32(b), kp,
                vp).compile()
        elif program == "window":
            compiled = jax.jit(_hybrid_window(cfg),
                               donate_argnums=(5, 6)).lower(
                params, i32(b), i32(b), i32(b, LFM2_TABLE), i32(b), kp,
                vp, i32()).compile()
        elif program == "mixed":
            compiled = jax.jit(functools.partial(
                llama.mixed_step, cfg, page_size=PAGE),
                donate_argnums=(9, 10)).lower(
                params, i32(b), i32(b), i32(b, LFM2_TABLE), i32(b),
                i32(CHUNK), i32(), i32(),
                llama.SlotPages(i32(LFM2_CHUNK_TABLE), i32()), kp,
                vp).compile()
        elif program == "chunk":
            compiled = jax.jit(functools.partial(
                llama.prefill_chunk, cfg, page_size=PAGE),
                donate_argnums=(4, 5)).lower(
                params, i32(CHUNK), i32(), i32(), kp, vp,
                llama.SlotPages(i32(LFM2_CHUNK_TABLE), i32())).compile()
        else:
            compiled = jax.jit(functools.partial(
                llama.prefill, cfg, page_size=PAGE),
                donate_argnums=(3, 4)).lower(
                params, i32(128), i32(), kp, vp,
                llama.SlotPages(i32(8), i32())).compile()
    assert dict(att.pallas_fallback_counts()) == before
    text = compiled.as_text()
    for scope in LFM2_SCOPES:
        assert scope in text, scope
    calls = [ln.strip() for ln in text.splitlines()
             if "tpu_custom_call" in ln and "attn_full" in ln]
    assert len(calls) == 1  # ONE attention body for the six layers
    if program in ("decode", "window", "mixed"):
        (pattern,) = _layer_metric_patterns(
            "gqa_%s_attn_roofline.extract" % (
                "mixed" if program == "mixed" else "decode"))
        assert re.search(pattern, calls[0]), calls[0][:200]
        assert "bf16[49152,16,512]" in calls[0]  # the pool flat, in place
    # two layer scans (the window's own scan around them) and the
    # operator's conditional in the expert layers' scan alone
    assert text.count(" while(") == (3 if program == "window" else 2)
    # of them the window's own is bounded by an operand, its trip count
    assert len(_loops_bounded_by_an_operand(text)) == (program == "window")
    assert len(re.findall(r" conditional\(", text)) >= 1
    assert "true_computation" in text or "branch_computations" in text
    # no program copies a pool: the whole-prompt prefill runs as ONE chunk
    # from position 0 (models/llama._hybrid_prefill says what its own form
    # cost behind the operator's conditional)
    assert not re.findall(r"bf16\[(?:6,8192|49152),16,512\]\S* copy\(", text)
    # the experts' three matmuls are ops/grouped_matmul's kernel in every
    # rung that holds rows, found by the name the cell's roofline metric
    # matches, and no ragged-dot is left beside it
    gmm_calls = [ln for ln in text.splitlines()
                 if "tpu_custom_call" in ln and "moe_experts" in ln]
    assert len(gmm_calls) == 3 and "ragged-dot" not in text
    (pattern,) = _layer_metric_patterns("moe_grouped_matmul_roofline.extract")
    assert all(re.search(pattern, ln.strip()) for ln in gmm_calls), (
        gmm_calls[0][:200])
    assert not re.search(r"bf16\[(18,64|1152),2,2048\]\S* copy\(", text)
    assert not re.search(r"= s8\[(18|6|22|2|704),\S* copy\(", text)
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes < 0.06e9
    stored = cfg.expert_dims_stored[1]
    want = (22 * 32 * 3 * 2048 * stored + 18 * 16.78e6 + 6 * 10.49e6
            + 2 * 44.04e6 + 134.2e6 + 2 * 6 * 8192 * 16 * 512 * 2
            + 18 * 64 * 2 * 2048 * 2)
    assert want < mem.argument_size_in_bytes < want + 0.05e9


def test_lfm2_state_snapshot_copies_stay_small_and_apart(one_chip):
    """The two copies between a state slot and the snapshot pool (PR 53) at
    LFM2's extents, compiled for a described v5e: the donated array is
    updated in place (nothing of the pool's or the states' size is copied),
    and no operation takes a shape by which the benchmark finds the
    convolution's own operations (`short_conv_roofline.extract` matches
    `[rows, 1 | 2, 2048]` blocks): the copies' device time stays out of it."""
    import re

    import jax
    import jax.numpy as jnp

    from dynamo_tpu.engine import kv_cache as kvc

    def arg(shape):
        return jax.ShapeDtypeStruct(shape, jnp.bfloat16, sharding=one_chip)

    states = ((), (arg((18, 64, 2, 2048)),))
    snaps = ((), (arg((512, 18, 1, 2, 2048)),))
    scalar = jax.ShapeDtypeStruct((), jnp.int32, sharding=one_chip)
    (pattern,) = _layer_metric_patterns("short_conv_roofline.extract")
    for fn, donated, args in (
            (kvc.save_state, (0,), (snaps, *states, scalar, scalar)),
            (kvc.restore_state, (0, 1), (*states, snaps, scalar, scalar))):
        text = jax.jit(functools.partial(fn, axis=1),
                       donate_argnums=donated).lower(*args).compile().as_text()
        ops = [line for line in text.splitlines() if " = " in line]
        assert ops and not [line for line in ops if re.search(pattern, line)]
        assert "input_output_alias" in text
        # one fused in-place update; nothing of either array's size copied
        assert text.count("dynamic-update-slice_fusion = ") == 1
        assert not [line for line in ops if re.search(
            r"= bf16\[(512,18,1|18,64),2,2048\]\S* copy\(", line)]


# MiniCPM-SALA as published (PR 56): 16 slots = 16 state slots of 24 Lightning
# states, a decode table of 49,152 tokens, the widest chunked-prompt table
SALA_SLOTS, SALA_TABLE, SALA_CHUNK_TABLE = 16, 3072, 3087
SALA_PAGES = 16384
SALA_SCOPES = ("sparse_pool_keys", "attn_sparse", "attn_qk_norm", "attn_gate",
               "lightning_in_proj", "lightning_gate_norm",
               "lightning_out_proj", "mlp_dense")
SALA_SCOPES_BY = {
    "decode": ("sparse_block_scores", "sparse_block_select",
               "attn_sparse_blocks", "lightning_update"),
    "window": ("sparse_block_scores", "sparse_block_select",
               "attn_sparse_blocks", "lightning_update"),
    "mixed": ("sparse_block_scores", "sparse_block_select",
              "attn_sparse_blocks", "attn_sparse_mask", "lightning_update",
              "lightning_scan"),
    "chunk": ("sparse_block_scores", "sparse_block_select",
              "attn_sparse_mask", "lightning_scan"),
    "prefill": ("lightning_scan",),
}


@pytest.mark.parametrize("program", ["decode", "mixed", "window", "prefill",
                                     "chunk"])
def test_sala_step_compiles_for_v5e_under_its_scope_names(one_chip, program):
    """The WHOLE published model's decode step, mixed step, 16-step window,
    whole-prompt prefill and prompt chunk at the cell's sizes (w8a8, all 32
    layers, 16 slots of 24 Lightning states and of 3,088 page sums in 8
    layers, 16,384 pages, a 256-token chunk, the 49,152-token tables) for a described
    v5e: no op leaves the kernels (no counted fallback), every span the
    docs name is in the HLO, the state update is the kernel
    `ssm_update_live` over the pool of states, the layers are ONE scan with
    ONE conditional over the operator's kind, no pool, no pooled-key array,
    no state array and no weight stack is copied, and the arguments are the
    memory the configuration file reckons, under 13 GB."""
    import re

    import jax
    import jax.numpy as jnp

    from dynamo_tpu.engine.kv_cache import KVCacheSpec
    from dynamo_tpu.models import llama, quant
    from dynamo_tpu.models.config import ModelConfig
    from dynamo_tpu.ops import attention as att

    def arg(shape, dtype):
        return jax.ShapeDtypeStruct(tuple(shape), dtype, sharding=one_chip)

    def i32(*shape):
        return arg(shape, jnp.int32)

    cfg = ModelConfig.from_model_name(os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        "benchmarks/chip/configs/minicpm-sala-w8a8-1chip"))
    b = SALA_SLOTS
    spec = KVCacheSpec.from_model(cfg, SALA_PAGES, PAGE, state_slots=b,
                                  pooled_key_pages=SALA_CHUNK_TABLE + 1)
    assert spec.shape == (8, SALA_PAGES, PAGE, 256)
    assert spec.ssm_shape == (32, 128, 128) and spec.conv_shape == ()
    assert spec.bytes_per_slot() == 24 * 32 * 128 * 128 * 4  # 50.3 MB
    assert spec.pooled_key_shape == (8, b, SALA_CHUNK_TABLE + 1, 256)
    assert not spec.state_kept_at_blocks
    params = {}
    for name, (shape, kind, _) in llama.param_specs(cfg).items():
        axes = quant.quant_axes(name)
        if axes and kind == "normal":
            params[name] = quant.QTensorA8(
                arg(shape, jnp.int8),
                arg([1 if i in axes else s for i, s in enumerate(shape)],
                    jnp.float32))
        else:
            params[name] = arg(shape, jnp.bfloat16)
    kp = llama.StatePools(
        arg(spec.shape, jnp.bfloat16),
        (arg((24, b) + spec.ssm_shape, jnp.float32),),
        (arg(spec.pooled_key_shape, jnp.float32),))
    vp = llama.StatePools(arg(spec.v_shape, jnp.bfloat16), ())
    before = dict(att.pallas_fallback_counts())
    with att.attention_context("pallas", None, 1):
        if program == "decode":
            compiled = jax.jit(functools.partial(
                llama.decode_step, cfg, page_size=PAGE),
                donate_argnums=(5, 6)).lower(
                params, i32(b), i32(b), i32(b, SALA_TABLE), i32(b), kp,
                vp).compile()
        elif program == "window":
            compiled = jax.jit(_hybrid_window(cfg),
                               donate_argnums=(5, 6)).lower(
                params, i32(b), i32(b), i32(b, SALA_TABLE), i32(b), kp,
                vp, i32()).compile()
        elif program == "mixed":
            compiled = jax.jit(functools.partial(
                llama.mixed_step, cfg, page_size=PAGE),
                donate_argnums=(9, 10)).lower(
                params, i32(b), i32(b), i32(b, SALA_TABLE), i32(b),
                i32(CHUNK), i32(), i32(),
                llama.SlotPages(i32(SALA_CHUNK_TABLE), i32()), kp,
                vp).compile()
        elif program == "chunk":
            compiled = jax.jit(functools.partial(
                llama.prefill_chunk, cfg, page_size=PAGE),
                donate_argnums=(4, 5)).lower(
                params, i32(CHUNK), i32(), i32(), kp, vp,
                llama.SlotPages(i32(SALA_CHUNK_TABLE), i32())).compile()
        else:
            compiled = jax.jit(functools.partial(
                llama.prefill, cfg, page_size=PAGE),
                donate_argnums=(3, 4)).lower(
                params, i32(128), i32(), kp, vp,
                llama.SlotPages(i32(8), i32())).compile()
    assert dict(att.pallas_fallback_counts()) == before
    text = compiled.as_text()
    for scope in SALA_SCOPES + SALA_SCOPES_BY[program]:
        assert scope in text, scope
    if program in ("decode", "window", "mixed"):
        calls = [ln.strip() for ln in text.splitlines()
                 if "tpu_custom_call" in ln and "lightning_update" in ln]
        assert len(calls) == 1 and "ssm_update_live" in calls[0]
        assert "f32[384,32,128,128]" in calls[0]  # the states flat, in place
        calls = [ln.strip() for ln in text.splitlines()
                 if "tpu_custom_call" in ln and "attn_sparse_blocks" in ln]
        assert len(calls) == 1  # ONE decode kernel for the eight layers
        assert "bf16[131072,16,256]" in calls[0]  # the pool flat, in place
    # no pool, pooled-key array, state array or weight stack is copied
    assert not re.findall(r"bf16\[(?:8,16384|131072),16,256\]\S* copy\(", text)
    # (the chunk-ALONE program, what an idle engine runs a prompt's chunks
    # through, relays the page sums out pages-minor at its entry and back at
    # its end, 0.4 GB each way, 2 ms of its 29: PERF.md section 7. The
    # programs that carry decode rows, the cell's, do not)
    sums_copies = re.findall(r"f32\[(?:8,16|128),3088,256\]\S* copy\(", text)
    assert len(sums_copies) == (2 if program == "chunk" else 0)
    assert not re.findall(r"f32\[(?:24,16|384),32,128,128\]\S* copy\(", text)
    assert not re.search(r"= s8\[(8|24|32),\S* copy\(", text)
    assert len(re.findall(r" conditional\(", text)) >= 1
    mem = compiled.memory_analysis()
    want = (8 * 52.4e6 + 24 * 83.9e6 + 32 * 201.3e6 + 601.7e6
            + 2 * 8 * SALA_PAGES * 16 * 256 * 2 + 24 * b * 32 * 128 * 128 * 4
            + 8 * b * (SALA_CHUNK_TABLE + 1) * 256 * 4)
    assert want < mem.argument_size_in_bytes < want + 0.08e9
    assert mem.argument_size_in_bytes < 13e9
    assert mem.temp_size_in_bytes < (0.5e9 if program == "chunk" else 0.15e9)
