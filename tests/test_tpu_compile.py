"""The ragged kernel at the benchmark cells' mixed-step shapes, compiled by
the TPU's own compiler for a v5e that is described, not attached.

Interpret mode proves a kernel's arithmetic on the CPU; Mosaic refuses what
it cannot tile or fit in VMEM only when it compiles. The compiler is
installed in the sandbox, so this guards the default mixed step of both
cells (PR 26) at no chip time. Nothing runs: no result, no timing.

The topology is described inside a fixture and in this file only (one
process may load libtpu; see the on-chip-measurement guide, section 2)."""

import functools
import os

import pytest

PAGE, HEAD_DIM, SLOTS, CHUNK, POOL_PAGES = 16, 128, 32, 256, 4096


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no libtpu here, or it is held elsewhere
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


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
