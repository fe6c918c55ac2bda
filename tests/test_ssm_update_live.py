"""The Mamba-2 one-token update over the LIVE slots only, in place
(`ops/ssm.update_live`, the kernel in interpret mode) against its XLA twin
over every slot (`ops/ssm.step_every_slot`: dt = 0 on the empty ones): live
rows' y and states within float32 rounding, an empty slot's state BIT FOR
BIT as it was, an empty row's y exactly 0; the same through a 16-step
`lax.scan` with the state as the donated carry (a decode window's form)
against 16 single steps; and which of the two `ops/ssm.update` takes under each
backend."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dynamo_tpu.ops import attention as att
from dynamo_tpu.ops import ssm

H, P, G, N = 4, 8, 2, 16
MASKS = {
    "none": lambda b: np.zeros((b,), bool),
    "one": lambda b: np.arange(b) == b // 2,
    "sparse": lambda b: np.arange(b) % 3 == 1,
    "prefix": lambda b: np.arange(b) < b // 2,
    "all": lambda b: np.ones((b,), bool),
}


def _inputs(b, seed=0):
    rng = np.random.default_rng(seed)
    return dict(
        x=jnp.asarray(rng.normal(size=(b, H, P)), jnp.bfloat16),
        dt=jnp.asarray(rng.uniform(1e-3, 1e-1, (b, H)), jnp.float32),
        a=-jnp.asarray(rng.uniform(1, 16, (H,)), jnp.float32),
        bm=jnp.asarray(rng.normal(size=(b, G, N)), jnp.bfloat16),
        cm=jnp.asarray(rng.normal(size=(b, G, N)), jnp.bfloat16),
        d=jnp.asarray(rng.normal(size=(H,)), jnp.float32),
        state=jnp.asarray(rng.normal(size=(b, H, P, N)), jnp.float32))


def _twin(i, live):
    return ssm.step_every_slot(i["x"], i["dt"], i["a"], i["bm"], i["cm"],
                               i["d"], i["state"], live)


def _kernel(i, live, **kw):
    return ssm.update_live(i["x"], i["dt"], i["a"], i["bm"], i["cm"], i["d"],
                           i["state"], live, ssm.live_slots(live),
                           interpret=True, **kw)


@pytest.mark.parametrize("slots", [5, 12])
@pytest.mark.parametrize("mask", list(MASKS))
@pytest.mark.parametrize("head_block", [None, 2])
def test_the_kernel_is_the_xla_twin_on_live_slots_and_touches_no_other(
        mask, slots, head_block):
    i = _inputs(slots)
    on = MASKS[mask](slots)
    live = jnp.asarray(on)
    want_y, want_s = (np.asarray(v) for v in _twin(i, live))
    got_y, got_s = (np.asarray(v) for v in jax.jit(
        lambda: _kernel(i, live, head_block=head_block))())
    np.testing.assert_allclose(got_s[on], want_s[on], rtol=1e-6, atol=1e-6)
    np.testing.assert_allclose(got_y[on], want_y[on], rtol=1e-6, atol=1e-5)
    before = np.asarray(i["state"])
    assert (got_s[~on] == before[~on]).all()  # bit for bit
    assert (got_y[~on] == 0).all() and not np.signbit(got_y[~on]).any()
    if on.any():  # the kernel did change what it was to change
        assert (got_s[on] != before[on]).any()


def test_live_slots_lists_the_live_ones_first_and_counts_them():
    got = ssm.live_slots(jnp.asarray([False, True, True, False, True]))
    assert got.ids.dtype == jnp.int32 and got.count.shape == (1,)
    assert list(np.asarray(got.ids)[:3]) == [1, 2, 4]
    assert int(got.count[0]) == 3
    none = ssm.live_slots(jnp.zeros((4,), bool))
    assert int(none.count[0]) == 0 and (np.asarray(none.ids) == 0).all()


@pytest.mark.parametrize("mask", ["sparse", "none", "all"])
def test_sixteen_steps_in_a_scan_with_the_state_donated_are_sixteen_single(
        mask):
    """A fused window's form: the live list built once, the state the
    donated carry of a `lax.scan`, each step's x hanging on the last y."""
    slots, steps = 6, 16
    i = _inputs(slots, seed=1)
    live = jnp.asarray(MASKS[mask](slots))

    def one(state, y):
        j = dict(i, x=i["x"] + 1e-3 * y, state=state)
        return _kernel(j, live)

    def window(state):
        def body(carry, _):
            y, state = one(*carry)
            return (state, y), y
        (state, _), ys = jax.lax.scan(
            body, (state, jnp.zeros((slots, H, P), jnp.float32)), None,
            length=steps)
        return state, ys

    got_s, got_ys = jax.jit(window, donate_argnums=(0,))(
        jnp.array(i["state"]))
    state, y = i["state"], jnp.zeros((slots, H, P), jnp.float32)
    single = jax.jit(one)
    for t in range(steps):
        y, state = single(state, y)
        np.testing.assert_array_equal(np.asarray(got_ys[t]), np.asarray(y))
    np.testing.assert_array_equal(np.asarray(got_s), np.asarray(state))
    on = np.asarray(live)
    assert (np.asarray(got_s)[~on] == np.asarray(i["state"])[~on]).all()


@pytest.mark.parametrize("backend,impl", [
    ("xla", "xla"), ("pallas_interpret", "pallas_interpret"),
    ("auto", "xla")])  # auto on a CPU
def test_update_follows_the_scoped_attention_backend(backend, impl):
    i = _inputs(5)
    live = jnp.asarray(MASKS["sparse"](5))
    before = att.attention_impl_counts().get(("ssm state update", impl), 0)
    with att.attention_context(None if backend == "auto" else backend, None,
                               1):
        assert ssm.update_backend(i["state"].shape) == impl
        got_y, got_s = ssm.update(i["x"], i["dt"], i["a"], i["bm"], i["cm"],
                                  i["d"], i["state"], live,
                                  ssm.live_slots(live))
    assert att.attention_impl_counts()[("ssm state update", impl)] == (
        before + 1)
    want_y, want_s = _twin(i, live)
    on = np.asarray(live)
    np.testing.assert_allclose(np.asarray(got_s), np.asarray(want_s),
                               rtol=1e-6, atol=1e-6)
    np.testing.assert_allclose(np.asarray(got_y)[on], np.asarray(want_y)[on],
                               rtol=1e-6, atol=1e-5)


def test_a_state_the_tpu_cannot_tile_is_a_counted_demotion():
    """On a TPU a head's state [P, N] has to tile (8, 128) float32; one
    that does not takes the XLA twin and is counted, not silent."""
    shape = (4, H, P, N)  # N = 16 lanes
    with att.attention_context("pallas", None, 1):
        assert ssm.update_backend(shape) == "xla"
        assert ssm.update_backend((4, 64, 64, 128)) == "pallas"
        i = _inputs(4)
        live = jnp.asarray(MASKS["all"](4))
        before = att.pallas_fallback_counts().get(
            ("ssm state update", "state_tiling"), 0)
        jax.eval_shape(lambda: ssm.update(
            i["x"], i["dt"], i["a"], i["bm"], i["cm"], i["d"], i["state"],
            live, ssm.live_slots(live)))
        assert att.pallas_fallback_counts()[
            ("ssm state update", "state_tiling")] == before + 1
    with att.attention_context("xla", None, 1):  # asked for: no demotion
        jax.eval_shape(lambda: ssm.update(
            i["x"], i["dt"], i["a"], i["bm"], i["cm"], i["d"], i["state"],
            live, ssm.live_slots(live)))
        assert att.pallas_fallback_counts()[
            ("ssm state update", "state_tiling")] == before + 1
