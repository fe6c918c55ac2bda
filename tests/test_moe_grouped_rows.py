"""The grouped expert layer's row ladder (ops/moe.row_rungs, PR 31): the
matmuls run over the smallest static row count that holds the rows the held
experts received, nothing runs where none did, and every rung gives what the
single rung of every assignment (the program before the ladder) gives."""

import functools
from unittest import mock

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dynamo_tpu.models import quant
from dynamo_tpu.ops import moe as moe_ops

# a share like the Kimi cell's: 8 of 128 experts held (1/16), top-8
T, K, X, XH, OFFSET, E, F, LAYERS = 24, 8, 128, 8, 16, 16, 24, 3
A = T * K  # 192 assignments, 12 expected here: rungs 0 | 32 | 128 | 192
RUNGS = (0, 32, 128, A)
MASKED = 2  # the last tokens of the masked variant are padding


@pytest.mark.parametrize("assignments,share,want", [
    (512, 1 / 16, (0, 32, 128, 512)),      # the Kimi cell's decode step
    (2560, 1 / 16, (0, 256, 2560)),        # its mixed step: 1024 > 512
    (A, XH / X, RUNGS),
    (64, 1 / 16, (0, 32, 64)),             # a rung not under A is left out
    (40, 0.5, (0, 32, 40)),
    (22, 1 / 4, (0, 22)),                  # tiny: under every rung
    (4096, 1.0, (0, 4096)),                # every expert held: all or none
    (2560, 1 / 64, (0, 64, 256, 2560)),
    (2048, 1 / 16, (0, 128, 512, 2048)),   # its 256-token chunk alone
    (32768, 1 / 16, (0, 2048, 32768)),
])
def test_row_rungs_follow_the_shapes(assignments, share, want):
    got = moe_ops.row_rungs(assignments, share)
    assert got == want
    assert got[0] == 0 and got[-1] == assignments
    assert list(got) == sorted(set(got))
    assert all(r >= 32 for r in got[1:-1])


def _weights(mode, stacked):
    lead = (LAYERS,) if stacked else ()
    ks = jax.random.split(jax.random.PRNGKey(5), 3)
    ws = [jax.random.normal(k, lead + (XH, a, b)) / np.sqrt(a)
          for k, (a, b) in zip(ks, [(E, F), (E, F), (F, E)])]
    if mode != "float32":
        cls = quant.qtensor_class(mode)
        ws = [quant.quantize(w, (len(lead) + 1,), cls) for w in ws]
    return ws


def _picks(n_held, tokens):
    """topi [T, K] whose first n_held assignments (token by token) are on
    held experts, distinct within a token; every other pick is held
    elsewhere. Tokens past `tokens` (padding) pick held experts only."""
    rng = np.random.default_rng(n_held)
    topi = np.empty((T, K), np.int64)
    for t in range(T):
        h = K if t >= tokens else int(np.clip(n_held - t * K, 0, K))
        topi[t, :h] = OFFSET + rng.choice(XH, h, replace=False)
        topi[t, h:] = OFFSET + XH + rng.choice(X - OFFSET - XH, K - h,
                                               replace=False)
        rng.shuffle(topi[t])
    return jnp.asarray(topi, jnp.int32)


@functools.lru_cache(maxsize=None)
def _layer(mode, stacked, single_rung):
    """The jitted layer; single_rung: the program before the ladder, the
    body over every assignment whatever the count."""
    rungs = (lambda a, share: (a,)) if single_rung else moe_ops.row_rungs

    def fn(x, topi, w, mask, layer, wg, wu, wd):
        with mock.patch.object(moe_ops, "row_rungs", rungs):  # while traced
            return moe_ops.moe_mlp_grouped(
                x, topi, w, wg, wu, wd, expert_offset=OFFSET, num_experts=X,
                token_mask=mask if stacked else None,
                layer=layer if stacked else None)
    return jax.jit(fn)


def _poison_untouched(ws, topi, live_tokens, layer, stacked):
    """NaN wherever no live row looks: the scales of experts no live row
    picked, and every other layer's. (Float weights are left alone: XLA's
    CPU expansion of ragged_dot multiplies every group's matrix by masked
    rows, and 0 x NaN is NaN there; the TPU's reads only touched groups.)"""
    if not isinstance(ws[0], quant.QTensor):
        return ws
    picked = np.unique(np.asarray(topi)[:live_tokens]) - OFFSET
    picked = picked[(picked >= 0) & (picked < XH)]
    out = []
    for w in ws:
        keep = np.zeros(w.scale.shape, bool)
        if stacked:
            keep[layer, picked] = True
        else:
            keep[picked] = True
        out.append(type(w)(w.q, jnp.where(keep, w.scale, jnp.nan)))
    return out


@pytest.mark.parametrize("n_held", [0, 1, 31, 32, 33, 129, A])
@pytest.mark.parametrize("mode", ["float32", "int8", "w8a8"])
@pytest.mark.parametrize("stacked", [False, True],
                         ids=["plain", "layer_and_mask"])
def test_every_rung_gives_what_the_single_rung_gives(n_held, mode, stacked):
    live_tokens = T - MASKED if stacked else T
    n_held = min(n_held, live_tokens * K)
    topi = _picks(n_held, live_tokens)
    x = jax.random.normal(jax.random.PRNGKey(9), (T, E))
    w = jax.random.uniform(jax.random.PRNGKey(2), (T, K)) + 0.1
    mask = jnp.arange(T) < live_tokens
    layer = jnp.int32(1)
    ws = _weights(mode, stacked)
    want, want_stats = _layer(mode, stacked, True)(x, topi, w, mask, layer,
                                                   *ws)
    # what nothing may read is NaN: the padding tokens' rows, and the
    # scales of every expert no live row picked
    x = jnp.where(mask[:, None], x, jnp.nan)
    got, stats = _layer(mode, stacked, False)(
        x, topi, w, mask, layer,
        *_poison_untouched(ws, topi, live_tokens, 1, stacked))
    assert np.isfinite(np.asarray(got)).all()
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)
    np.testing.assert_array_equal(np.asarray(got)[live_tokens:], 0)
    stats, want_stats = np.asarray(stats), np.asarray(want_stats)
    np.testing.assert_array_equal(stats[:5], want_stats[:5])
    assert stats[1] == n_held and want_stats[5] == A
    rung = next(r for r in RUNGS if r >= n_held)
    assert stats[5] == rung
    if n_held:
        assert np.abs(np.asarray(got)[0]).max() > 0  # token 0 was computed


def test_every_assignment_held_takes_the_last_rung_and_drops_nothing():
    """The worst case is the program before the ladder: all T*K rows."""
    topi = _picks(A, T)
    x = jax.random.normal(jax.random.PRNGKey(9), (T, E))
    w = jax.random.uniform(jax.random.PRNGKey(2), (T, K)) + 0.1
    wg, wu, wd = _weights("float32", False)
    got, stats = _layer("float32", False, False)(
        x, topi, w, None, None, wg, wu, wd)
    combine = moe_ops.scatter_combine(topi - OFFSET, w, XH, x.dtype)
    want = moe_ops.moe_mlp_dense(x, combine, wg, wu, wd)
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)
    assert np.asarray(stats).tolist() == [A, A, T, XH, 1, A]


@pytest.mark.parametrize("n_held", [1, 31, 32, 33, 129, A])
def test_int32_accumulators_of_live_rows_are_the_same_on_every_rung(
        n_held, monkeypatch):
    """w8a8: a live row's int32 sums over the first R sorted rows are the
    integers the sums over all A rows give, bit for bit."""
    rung = next(r for r in RUNGS if r >= n_held)
    rng = np.random.default_rng(n_held)
    sizes = np.bincount(rng.integers(0, XH, n_held), minlength=XH)
    row_expert = np.minimum(np.repeat(np.arange(XH + 1),
                                      list(sizes) + [A - n_held]), XH - 1)
    rows = jax.random.normal(jax.random.PRNGKey(3), (A, E))
    w_gate = _weights("w8a8", False)[0]
    seen = []
    real = jax.lax.ragged_dot

    def recording(*args, **kw):
        seen.append(real(*args, **kw))
        return seen[-1]

    monkeypatch.setattr(jax.lax, "ragged_dot", recording)
    sizes = jnp.asarray(sizes, jnp.int32)
    row_expert = jnp.asarray(row_expert, jnp.int32)
    part = moe_ops._grouped_dot(rows[:rung], w_gate, sizes,
                                row_expert[:rung])
    whole = moe_ops._grouped_dot(rows, w_gate, sizes, row_expert)
    acc_part, acc_whole = seen
    assert acc_part.dtype == jnp.int32 and acc_part.shape == (rung, F)
    assert acc_whole.shape == (A, F)
    np.testing.assert_array_equal(np.asarray(acc_part)[:n_held],
                                  np.asarray(acc_whole)[:n_held])
    np.testing.assert_array_equal(np.asarray(part)[:n_held],
                                  np.asarray(whole)[:n_held])
    assert np.abs(np.asarray(acc_part)[:n_held]).max() > 0


def test_ladder_under_the_expert_axis_rules():
    """The held experts sharded over `expert` and their features over
    `model` (parallel/sharding.py's rules for moe_w_*), GSPMD on the virtual
    CPU mesh: the rung is chosen from a replicated count, and the layer
    gives what one device gives."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    n_held = 33
    topi = _picks(n_held, T)
    x = jax.random.normal(jax.random.PRNGKey(9), (T, E))
    w = jax.random.uniform(jax.random.PRNGKey(2), (T, K)) + 0.1
    ws = _weights("float32", False)
    fn = _layer("float32", False, False)
    want, want_stats = fn(x, topi, w, None, None, *ws)
    mesh = Mesh(np.array(jax.devices()[:4]).reshape(2, 2),
                ("expert", "model"))

    def put(a, *spec):
        return jax.device_put(a, NamedSharding(mesh, P(*spec)))

    got, stats = fn(put(x), put(topi), put(w), None, None,
                    put(ws[0], "expert", None, "model"),
                    put(ws[1], "expert", None, "model"),
                    put(ws[2], "expert", "model", None))
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)
    np.testing.assert_array_equal(stats, want_stats)
    assert np.asarray(stats)[5] == 128
