"""MoE dispatch paths: the router's combine matrix, and the model's dense
path against the grouped one."""

import jax
import jax.numpy as jnp
import numpy as np

from dynamo_tpu.ops import moe


def test_topk_combine_rows_sum_to_one():
    logits = jax.random.normal(jax.random.PRNGKey(0), (16, 8))
    c = moe.topk_combine(logits, 2, jnp.float32)
    assert c.shape == (16, 8)
    np.testing.assert_allclose(np.sum(c, axis=-1), 1.0, rtol=1e-5)
    assert int(np.count_nonzero(c)) == 32  # exactly k entries per row


def test_model_mlp_moe_paths_agree():
    """The model's _mlp picks its expert path from shapes (the tiny preset:
    2 of 4 experts, dense); the grouped path on the same routing is the
    same sum, and a masked padding row weighs nothing on either."""
    import dataclasses

    from dynamo_tpu.models import llama
    from dynamo_tpu.models.config import PRESETS

    cfg = dataclasses.replace(PRESETS["tiny-moe-debug"], dtype="float32")
    assert cfg.is_moe and not cfg.moe_grouped
    params = llama.init_params(cfg, jax.random.PRNGKey(0))
    lp = {k: v[0] for k, v in llama._layer_params(params).items()}

    t = 64
    xs = jax.random.normal(jax.random.PRNGKey(1), (t, cfg.hidden_size),
                           dtype=jnp.float32) * 0.1
    mask = jnp.arange(t) < t - 8
    dense, counts = llama._mlp(cfg, lp, xs, token_mask=mask)
    assert counts is None
    logits = jnp.einsum("te,ex->tx", xs, lp["router"],
                        preferred_element_type=jnp.float32)
    topi, weights = moe.route_topk(logits, cfg.num_experts_per_tok,
                                   renormalize=cfg.norm_topk_prob)
    grouped, stats = moe.moe_mlp_grouped(
        xs, topi, weights, lp["moe_w_gate"], lp["moe_w_up"],
        lp["moe_w_down"], token_mask=mask)
    np.testing.assert_allclose(np.asarray(dense), np.asarray(grouped),
                               rtol=1e-4, atol=1e-5)
    assert not np.any(np.asarray(dense[t - 8:]))
    assert np.any(np.asarray(dense[:t - 8]))
