"""Named-sharding rules for llama-family parameters, KV cache, and activations.

Megatron-style tensor parallelism expressed declaratively: column-parallel
projections shard their output feature dim on `model`, row-parallel shard the
input feature dim; XLA inserts the psum/all-gather collectives over ICI.
This replaces the NCCL tensor-parallel groups inside the reference's consumed
engines (SURVEY.md §2d).
"""

from __future__ import annotations

from typing import Any, Dict

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

# Param-tree leaf name -> PartitionSpec. Layer-stacked params carry a leading
# `num_layers` axis (scanned over), which is never sharded.
PARAM_RULES: Dict[str, P] = {
    # [V, E]: shard vocab so the embed table and (tied) lm_head split evenly.
    "embed": P("model", None),
    "lm_head": P(None, "model"),  # [E, V]
    "final_norm": P(None),
    # attention (leading L axis from the layer stack)
    "attn_norm": P(None, None),
    "wq": P(None, None, "model", None),  # [L, E, H, D] column-parallel
    "wk": P(None, None, "model", None),  # [L, E, KV, D]
    "wv": P(None, None, "model", None),
    "wo": P(None, "model", None, None),  # [L, H, D, E] row-parallel
    "bq": P(None, "model", None),
    "bk": P(None, "model", None),
    "bv": P(None, "model", None),
    "q_norm": P(None, None),
    "k_norm": P(None, None),
    # MLA (latent attention): head-carrying projections shard on `model`;
    # the shared latent down-projection and norm replicate (every shard
    # scores its local heads against the full latent row)
    "wq_mla": P(None, None, "model", None),   # [L, E, H, nope+rope]
    # query low-rank path: the shared down-projection and its norm
    # replicate like w_kv_a, the per-head up-projection shards its heads
    "wq_a": P(None, None, None),              # [L, E, q_lora]
    "q_a_norm": P(None, None),
    "wq_b": P(None, None, "model", None),     # [L, q_lora, H, nope+rope]
    "w_kv_a": P(None, None, None),            # [L, E, lora+rope] shared
    "kv_a_norm": P(None, None),
    "w_uk": P(None, "model", None, None),     # [L, H, nope, lora]
    "w_uv": P(None, "model", None, None),     # [L, H, lora, v]
    # the sparse-attention indexer (DeepSeek-V3.2) replicates: every shard
    # needs every index head's score to make the same selection
    "idx_wq_b": P(None, None, None, None),    # [L, q_lora, Hi, Di]
    "idx_wk": P(None, None, None),            # [L, E, Di]
    "idx_k_norm": P(None, None),
    "idx_k_bias": P(None, None),
    "idx_w": P(None, None, None),             # [L, E, Hi]
    # dense MLP
    "mlp_norm": P(None, None),
    "w_gate": P(None, None, "model"),  # [L, E, F] column-parallel
    "w_up": P(None, None, "model"),
    "w_down": P(None, "model", None),  # [L, F, E] row-parallel
    # MoE: experts shard on `expert`, features on `model`
    # the router and its selection bias keep the model's whole width on
    # every chip; the expert weights' X axis is the experts HELD: under an
    # ep > 1 mesh each shard runs the same layer over its own slice
    "router": P(None, None, None),  # [L, E, num_experts]
    "router_bias": P(None, None),   # [L, num_experts]
    "moe_w_gate": P(None, "expert", None, "model"),  # [L, X, E, F]
    "moe_w_up": P(None, "expert", None, "model"),
    "moe_w_down": P(None, "expert", "model", None),  # [L, X, F, E]
}

# KV cache: [L, pages, page_size, KV_heads*head_dim] — the fused head-major
# lane axis shards on `model` so each TP shard appends/reads only its local
# heads' lanes; pages stay local to the shard (no cross-device traffic in the
# decode inner loop).
KV_SPEC = P(None, None, None, "model")
# decode activations: batch on data, hidden replicated across model
ACT_SPEC = P("data", None)


def param_specs(params: Dict[str, Any]) -> Dict[str, Any]:
    """Map a param tree to PartitionSpecs by leaf name (dict key).

    Quantized weights (models.quant.QTensor) get a spec PER FIELD: the int8
    `q` follows the weight rule; the keepdims `scale` follows the same rule
    with size-1 (contracted) axes unsharded."""
    from dynamo_tpu.models.quant import QTensor

    def spec_for(name: str, x):
        # a leading dense layer's leaf ("dense." prefix) follows its plain
        # name's rule: both stacks carry the same leading layer axis
        name = name.rsplit(".", 1)[-1]
        if isinstance(x, QTensor):
            rule = PARAM_RULES.get(name, P(*([None] * x.q.ndim)))
            scale_rule = P(*(
                None if x.scale.shape[i] == 1 else rule[i]
                for i in range(x.scale.ndim)
            ))
            # preserve the subclass (QTensorA8): pytree node types must
            # match the param tree's for spec/param tree.map pairing
            return type(x)(rule, scale_rule)
        if name in PARAM_RULES:
            return PARAM_RULES[name]
        return P(*([None] * x.ndim))

    return {k: spec_for(k, v) for k, v in params.items()}


# axis names any repo mesh can carry; a PARAM_RULES axis outside this set
# is a typo and must stay LOUD (reach NamedSharding and raise), never be
# silently replicated
KNOWN_MESH_AXES = frozenset({"data", "expert", "model", "seq"})


def _fit_spec(spec: P, shape, mesh: Mesh) -> P:
    """Drop (replicate) spec axes that don't fit this mesh: axes whose mesh
    extent doesn't divide the dim — e.g. KV-head projections when tp >
    num_kv_heads (GQA over-sharding) — and KNOWN axes the mesh doesn't
    carry — e.g. 'expert' rules on the ('seq','model') long-context mesh.
    Either way the weight replicates and downstream sharding still works;
    unknown axis names pass through so typos fail loudly."""
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    fixed = []
    for i, axis in enumerate(spec):
        if (isinstance(axis, str) and axis not in sizes
                and axis in KNOWN_MESH_AXES):
            fixed.append(None)
            continue
        n = sizes.get(axis, 1) if isinstance(axis, str) else 1
        fixed.append(axis if (axis is None or shape[i] % n == 0) else None)
    return P(*fixed)


def shard_params(params: Dict[str, Any], mesh: Mesh) -> Dict[str, Any]:
    specs = param_specs(params)
    shardings = jax.tree.map(
        lambda s, x: NamedSharding(mesh, _fit_spec(s, x.shape, mesh)),
        specs, dict(params),
        is_leaf=lambda s: isinstance(s, P),
    )
    return {
        k: jax.device_put(v, shardings[k]) for k, v in params.items()
    }


def kv_sharding(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, KV_SPEC)


def replicated(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, P())
