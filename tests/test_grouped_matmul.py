"""ops/grouped_matmul.py: the W8A8 grouped matmul of our own against
`jax.lax.ragged_dot`, under the Pallas interpreter on the CPU, and the gate
that hands ops/moe's expert layer to it."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dynamo_tpu.models import quant
from dynamo_tpu.ops import attention as att
from dynamo_tpu.ops import grouped_matmul as gm
from dynamo_tpu.ops import moe


def _case(rows, experts, k, n, layers, seed=0):
    rng = np.random.default_rng(seed)
    wq = jnp.asarray(rng.integers(-127, 127, (layers, experts, k, n),
                                  dtype=np.int8))
    ws = jnp.asarray(rng.uniform(0.5, 1.5, (layers, experts, 1, n)
                                 ).astype(np.float32))
    xq = jnp.asarray(rng.integers(-127, 127, (rows, k), dtype=np.int8))
    xs = jnp.asarray(rng.uniform(0.5, 1.5, (rows, 1)).astype(np.float32))
    return xq, xs, wq, ws


@pytest.mark.parametrize("rows,tile,layer,sizes", [
    (64, 32, 1, [5, 0, 40, 7]),       # an expert across two tiles, one empty
    (64, 32, 2, [0, 0, 0, 64]),       # one expert holds every row
    (64, 32, 0, [1, 1, 1, 1]),        # four experts in one tile, a tail
    (256, 128, 1, [130, 0, 3, 10]),   # the shipped tile height
    (128, 32, 0, [32, 32, 32, 32]),   # groups that end on tile boundaries
], ids=["straddle", "one_expert", "one_tile", "tile128", "aligned"])
def test_kernel_gives_ragged_dots_numbers(rows, tile, layer, sizes):
    x = len(sizes)
    k, n, layers = 128, 256, 3
    xq, xs, wq, ws = _case(rows, x, k, n, layers)
    sizes = jnp.asarray(sizes, jnp.int32)
    got = gm.grouped_matmul(xq, xs, wq, ws, gm.pairs(sizes, rows, tile),
                            jnp.int32(layer), out_dtype=jnp.float32,
                            interpret=True)
    flat = jnp.zeros((layers * x,), jnp.int32).at[
        layer * x:(layer + 1) * x].set(sizes)
    want = jax.lax.ragged_dot(xq, wq.reshape(-1, k, n), flat,
                              preferred_element_type=jnp.int32)
    row_expert = jnp.repeat(jnp.arange(x), sizes,
                            total_repeat_length=rows) + layer * x
    want = (want.astype(jnp.float32) * xs) * ws.reshape(-1, n)[row_expert]
    held = int(sizes.sum())
    np.testing.assert_array_equal(np.asarray(got[:held]),
                                  np.asarray(want[:held]))


def test_pairs_name_every_expert_tile_once_and_pad_with_the_last():
    work = gm.pairs(jnp.asarray([5, 0, 40, 7], jnp.int32), 64, 32)
    n = int(work.count[0])
    assert n == 4 and work.expert.shape == (2 + 4 - 1,)
    assert list(zip(work.expert[:n].tolist(), work.tile[:n].tolist())) == [
        (0, 0), (2, 0), (2, 1), (3, 1)]
    # a pair past the real ones asks for no new block
    assert (work.expert[n:].tolist(), work.tile[n:].tolist()) == ([3], [1])
    assert work.start.tolist() == [0, 5, 5, 45]
    assert work.end.tolist() == [5, 5, 45, 52]


@pytest.mark.parametrize("backend,rows,experts,k,n,took", [
    ("pallas", 256, 32, 2048, 2048, True),      # LFM2-8B-A1B's layer
    ("pallas_interpret", 1280, 32, 2048, 2048, True),
    ("xla", 256, 32, 2048, 2048, False),        # no kernel backend
    ("pallas", 256, 256, 3072, 1024, False),    # Laguna: 256 experts
    ("pallas", 256, 24, 7168, 2048, False),     # Kimi: 14 MiB a matrix
    ("pallas", 16, 8, 64, 32, False),           # a tiny preset
    ("pallas", 200, 32, 2048, 2048, False),     # rows no tile multiple
], ids=["lfm2_decode", "lfm2_mixed", "xla", "laguna", "kimi", "tiny",
        "ragged_rows"])
def test_the_gate_is_the_measured_shape_alone(backend, rows, experts, k, n,
                                              took):
    with att.attention_context(backend, None, 1):
        assert (gm.serves(rows, experts, k, n) is not None) == took


@pytest.mark.parametrize("layer", [None, 1], ids=["one_layer", "stack"])
def test_the_expert_layer_is_the_same_through_the_kernel(layer):
    """moe_mlp_grouped at lane-aligned widths: under the interpreter's
    backend the three matmuls are the kernel's and a token's results are
    gathered back, under xla ragged_dot's and a scatter-add. The int32
    products are the same; the kernel's branch keeps the down projection's
    rows in float32 until a token's k are summed where the other rounds
    each to bfloat16 first, so the results agree to a bfloat16 step or two;
    the counts are the same."""
    t, k, e, f, x = 64, 2, 128, 256, 4
    rng = np.random.default_rng(3)

    def q(shape):
        return quant.QTensorA8(
            jnp.asarray(rng.integers(-127, 127, shape, dtype=np.int8)),
            jnp.asarray(rng.uniform(0.001, 0.002, shape[:-2] + (1,) +
                                    shape[-1:]).astype(np.float32)))

    lead = () if layer is None else (3,)
    wg, wu, wd = q(lead + (x, e, f)), q(lead + (x, e, f)), q(lead + (x, f, e))
    xin = jnp.asarray(rng.standard_normal((t, e)), jnp.bfloat16)
    topi = jnp.asarray(rng.integers(0, x, (t, k)), jnp.int32)
    wts = jnp.asarray(rng.uniform(0.2, 0.8, (t, k)), jnp.float32)
    mask = jnp.arange(t) < 50
    out = {}
    for backend in ("xla", "pallas_interpret"):
        before = att.attention_impl_counts().get(
            ("grouped_matmul", backend), 0)
        with att.attention_context(backend, None, 1):
            out[backend] = moe.moe_mlp_grouped(
                xin, topi, wts, wg, wu, wd, token_mask=mask,
                layer=None if layer is None else jnp.int32(layer))
        noted = att.attention_impl_counts().get(
            ("grouped_matmul", backend), 0) - before
        assert noted == (1 if backend == "pallas_interpret" else 0)
    np.testing.assert_allclose(np.asarray(out["xla"][0], np.float32),
                               np.asarray(out["pallas_interpret"][0],
                                          np.float32), rtol=2 ** -6,
                               atol=2 ** -6)
    # rows of masked tokens come back exactly zero either way
    assert not np.asarray(out["pallas_interpret"][0], np.float32)[50:].any()
    np.testing.assert_array_equal(np.asarray(out["xla"][1]),
                                  np.asarray(out["pallas_interpret"][1]))
    assert float(jnp.max(jnp.abs(out["xla"][0].astype(jnp.float32)))) > 0
