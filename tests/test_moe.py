"""MoE dispatch paths: dense vs capacity-gather equivalence, drop semantics,
and expert-parallel sharding on a multi-device mesh."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dynamo_tpu.ops import moe
from dynamo_tpu.parallel.mesh import MeshConfig, build_mesh


def _weights(key, x_, e, f, dtype=jnp.float32):
    k1, k2, k3 = jax.random.split(key, 3)
    s = 1.0 / np.sqrt(e)
    return (
        (jax.random.normal(k1, (x_, e, f)) * s).astype(dtype),
        (jax.random.normal(k2, (x_, e, f)) * s).astype(dtype),
        (jax.random.normal(k3, (x_, f, e)) / np.sqrt(f)).astype(dtype),
    )


def test_topk_combine_rows_sum_to_one():
    logits = jax.random.normal(jax.random.PRNGKey(0), (16, 8))
    c = moe.topk_combine(logits, 2, jnp.float32)
    assert c.shape == (16, 8)
    np.testing.assert_allclose(np.sum(c, axis=-1), 1.0, rtol=1e-5)
    assert int(np.count_nonzero(c)) == 32  # exactly k entries per row


def test_dropping_matches_dense_at_full_capacity():
    t, x_, e, f, k = 24, 4, 16, 32, 2
    key = jax.random.PRNGKey(1)
    xs = jax.random.normal(key, (t, e))
    logits = jax.random.normal(jax.random.PRNGKey(2), (t, x_))
    combine = moe.topk_combine(logits, k, jnp.float32)
    wg, wu, wd = _weights(jax.random.PRNGKey(3), x_, e, f)
    dense = moe.moe_mlp_dense(xs, combine, wg, wu, wd)
    # capacity == T: nothing can be dropped -> numerically identical compute
    dropped = moe.moe_mlp_dropping(xs, combine, wg, wu, wd, capacity=t)
    np.testing.assert_allclose(np.asarray(dense), np.asarray(dropped),
                               rtol=1e-4, atol=1e-5)


def test_dropping_close_to_dense_at_typical_capacity():
    # with near-uniform routing and cf 1.25 almost nothing drops
    t, x_, e, f, k = 128, 8, 16, 32, 2
    xs = jax.random.normal(jax.random.PRNGKey(4), (t, e)) * 0.1
    logits = jax.random.normal(jax.random.PRNGKey(5), (t, x_)) * 0.01
    combine = moe.topk_combine(logits, k, jnp.float32)
    wg, wu, wd = _weights(jax.random.PRNGKey(6), x_, e, f)
    cap = moe.expert_capacity(t, x_, k, 1.25)
    assert cap < t
    dense = moe.moe_mlp_dense(xs, combine, wg, wu, wd)
    dropped = moe.moe_mlp_dropping(xs, combine, wg, wu, wd, capacity=cap)
    # dropped tokens lose one of their k experts; bound the relative error
    err = np.linalg.norm(np.asarray(dense - dropped)) / np.linalg.norm(
        np.asarray(dense)
    )
    assert err < 0.15, err


def test_expert_capacity_static_shape():
    assert moe.expert_capacity(128, 8, 2, 1.25) == 40  # 128*2/8*1.25 -> 40
    assert moe.expert_capacity(8, 8, 2, 1.25) == 8  # floor at 8, cap at T
    assert moe.expert_capacity(1024, 8, 2, 1.0) == 256


@pytest.mark.parametrize("ep", [2, 4])
def test_dropping_under_expert_parallel_mesh(ep):
    """jit the gather path with moe weights sharded over the expert axis."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    t, x_, e, f, k = 64, 4, 16, 32, 2
    mesh = build_mesh(MeshConfig(expert_parallel=ep))
    xs = jax.random.normal(jax.random.PRNGKey(7), (t, e))
    logits = jax.random.normal(jax.random.PRNGKey(8), (t, x_)) * 0.01
    combine = moe.topk_combine(logits, k, jnp.float32)
    wg, wu, wd = _weights(jax.random.PRNGKey(9), x_, e, f)
    ref = moe.moe_mlp_dropping(xs, combine, wg, wu, wd,
                               capacity=moe.expert_capacity(t, x_, k, 1.25))

    ex = NamedSharding(mesh, P("expert", None, None))
    wg_s, wu_s, wd_s = (jax.device_put(w, ex) for w in (wg, wu, wd))
    rep = NamedSharding(mesh, P())
    xs_s, combine_s = jax.device_put(xs, rep), jax.device_put(combine, rep)

    fn = jax.jit(
        lambda a, c, g, u, d: moe.moe_mlp_dropping(
            a, c, g, u, d, capacity=moe.expert_capacity(t, x_, k, 1.25)
        )
    )
    out = fn(xs_s, combine_s, wg_s, wu_s, wd_s)
    np.testing.assert_allclose(np.asarray(ref), np.asarray(out), rtol=1e-4,
                               atol=1e-5)


def test_model_mlp_moe_paths_agree():
    """The model's _mlp must produce consistent results for prefill-sized
    (gather path) and decode-sized (dense path) token counts."""
    import dataclasses

    from dynamo_tpu.models import llama
    from dynamo_tpu.models.config import PRESETS

    cfg = dataclasses.replace(PRESETS["tiny-moe-debug"], dtype="float32",
                              moe_capacity_factor=4.0)  # no drops
    params = llama.init_params(cfg, jax.random.PRNGKey(0))
    lp = {k: v[0] for k, v in llama._layer_params(params).items()}

    t = 64
    xs = jax.random.normal(jax.random.PRNGKey(1), (t, cfg.hidden_size),
                           dtype=jnp.float32) * 0.1
    # cf=4 -> cap==t -> dense
    big, _ = llama._mlp(cfg, lp, xs, allow_capacity=True)
    cfg_drop = dataclasses.replace(cfg, moe_capacity_factor=1.25)
    small, _ = llama._mlp(cfg_drop, lp, xs,
                          allow_capacity=True)  # gather path
    err = np.linalg.norm(np.asarray(big - small)) / np.linalg.norm(np.asarray(big))
    assert err < 0.15, err
    # decode path (allow_capacity=False) must ignore the capacity factor
    dec, _ = llama._mlp(cfg_drop, lp, xs)
    np.testing.assert_allclose(np.asarray(big), np.asarray(dec), rtol=1e-4,
                               atol=1e-5)
