"""Weight-only int8 quantization for the serving engine.

Why: the north-star model (Llama-3-8B, BASELINE.json config #3) needs ~16 GiB
of bf16 weights — more than a v5e chip's HBM. Symmetric per-channel int8
halves that to ~8 GiB (and halves the decode weight-stream bytes, which the
roofline says is the dominant decode cost at short context), putting the 8B
class on-chip with KV room to spare. The reference gets the same effect from
TRT-LLM engine quantization recipes; here it is a loader-level transform.

Design (TPU-first):
- **Scales live on the output channels** (we quantize over the contraction
  axes), so every matmul runs as `einsum(x, w_int8 -> accum) * scale_out`:
  the int8->bf16 convert fuses into the MXU operand load and the scale is a
  cheap multiply on the (small) output — the dequantized weight is NEVER
  materialized in HBM, preserving the 2x bandwidth win.
- `QTensor` is a NamedTuple, hence a transparent pytree: layer-stacked
  quantized weights scan (`lax.scan`) and shard (`NamedSharding`) exactly
  like plain arrays; `dynamo_tpu.parallel.sharding` derives the scale's
  PartitionSpec from the weight rule by dropping contracted (size-1) axes.
- Quantization happens on the HOST (loader pins it to the CPU backend), so
  an 8B checkpoint never exists in bf16 on the chip.
"""

from __future__ import annotations

from typing import Dict, NamedTuple, Tuple

import jax
import jax.numpy as jnp


class QTensor(NamedTuple):
    """Symmetric per-channel int8 weight: `w ≈ q * scale`.

    `q` keeps the original weight shape; `scale` keeps the original rank with
    size-1 contraction axes (keepdims), so scanning a layer-stacked QTensor
    slices both leaves coherently.
    """

    q: jax.Array  # int8, original shape
    scale: jax.Array  # f32, keepdims over the quantization (contraction) axes


class QTensorA8(QTensor):
    """W8A8 variant: same storage, but matmuls quantize ACTIVATIONS per-token
    to int8 and contract on the native int8 MXU path (int8 x int8 -> int32),
    rescaling by (activation scale x weight scale) on the small output.

    Why: the weight-only path's int8 -> bf16 convert runs on the VPU, which
    feeds the MXU far slower than a bf16 weight stream — measured ~9x slower
    than dense bf16 on v5e for a [64,4096]x[4096,14336] matmul, vs ~2.4x
    FASTER for this native-int8 path. Weight-only stays exact w.r.t. the
    stored int8 weights; W8A8 adds per-token activation rounding error (the
    standard serving trade, cf. TRT-LLM's int8 engines on the reference
    stack). Subclass identity selects the path at trace time (the pytree
    treedef carries the class, so jit specializes per mode)."""


# Param-name -> contraction axes of the STACKED tensor (leading L axis where
# applicable). Everything else (norms, biases, router — all tiny) stays in
# the model dtype.
QUANT_AXES: Dict[str, Tuple[int, ...]] = {
    "embed": (1,),  # [V, E] — per-vocab-row (also correct for the tied head)
    "lm_head": (0,),  # [E, V]
    "wq": (1,),  # [L, E, H, D]
    "wk": (1,),
    "wv": (1,),
    "wo": (1, 2),  # [L, H, D, E]
    # MLA projections (qeinsum-served; W_UK/W_UV stay unquantized — they
    # run in f32 inside the absorbed-query path)
    "wq_mla": (1,),   # [L, E, H, nope+rope]
    "wq_a": (1,),     # [L, E, q_lora] query low-rank path (Kimi-K2 / V3)
    "wq_b": (1,),     # [L, q_lora, H, nope+rope]
    "w_kv_a": (1,),   # [L, E, lora+rope]
    # the sparse-attention indexer's two larger projections (its head
    # weights idx_w, its LayerNorm and W_UK / W_UV stay in the model dtype)
    "idx_wq_b": (1,),  # [L, q_lora, Hi, Di]
    "idx_wk": (1,),    # [L, E, Di]
    "w_gate": (1,),  # [L, E, F]
    "w_up": (1,),
    "w_down": (1,),  # [L, F, E]
    # a hybrid model's Mamba-2 projections (the conv and the per-head
    # vectors stay in their own dtypes)
    "ssm_in": (1,),   # [L_M, E, z | x B C | dt]
    "ssm_out": (1,),  # [L_M, d_in, E]
    # a gated short convolution's two projections (its taps stay in the
    # model's dtype)
    "conv_in": (1,),   # [L_c, E, B | C | u]
    "conv_out": (1,),  # [L_c, E, E]
    # minicpm_sala's projections with their heads side by side, and its
    # output gates (sparse and Lightning operators alike)
    "w_q": (1,),  # [L, E, H * D]
    "w_k": (1,),  # [L, E, KV * D] (a Lightning layer: H * D)
    "w_v": (1,),
    "w_og": (1,),  # [L, E, H * D]
    "moe_w_gate": (2,),  # [L, X, E, F]
    "moe_w_up": (2,),
    "moe_w_down": (2,),  # [L, X, F, E]
}


def quantize(w: jax.Array, axes: Tuple[int, ...], cls=QTensor) -> QTensor:
    """Symmetric int8 over `axes` (the contraction dims), per-channel scales."""
    w32 = w.astype(jnp.float32)
    amax = jnp.max(jnp.abs(w32), axis=axes, keepdims=True)
    scale = jnp.where(amax > 0, amax / 127.0, 1.0).astype(jnp.float32)
    q = jnp.clip(jnp.round(w32 / scale), -127, 127).astype(jnp.int8)
    return cls(q, scale)


def qtensor_class(mode: str):
    """Map a quantization mode name to its QTensor class."""
    return QTensorA8 if mode == "w8a8" else QTensor


def quantize_params(params: Dict[str, jax.Array], mode: str = "int8"
                    ) -> Dict[str, jax.Array]:
    """Quantize every weight named in QUANT_AXES; pass the rest through.
    A leading dense layer's leaf ("dense." prefix) follows its plain
    name's entry: the stacks share their axes."""
    cls = qtensor_class(mode)
    return {
        k: quantize(v, quant_axes(k), cls) if quant_axes(k) else v
        for k, v in params.items()
    }


def quant_axes(name: str):
    """Contraction axes of the stacked leaf `name`, or None if it stays
    in the model dtype."""
    return QUANT_AXES.get(name.rsplit(".", 1)[-1])


def is_quantized(params: Dict) -> bool:
    return any(isinstance(v, QTensor) for v in params.values())


def _scale_to_out(spec_in: str, out: str, scale: jax.Array):
    """Reorder a keepdims scale (labels `spec_in`) to broadcast over `out`."""
    keep = "".join(c for c in out if c in spec_in)
    flat = jnp.einsum(f"{spec_in}->{keep}", scale)
    shape = tuple(flat.shape[keep.index(c)] if c in keep else 1 for c in out)
    return flat.reshape(shape)


def einsum(spec: str, x: jax.Array, w) -> jax.Array:
    """`jnp.einsum(spec, x, w)` that understands QTensor weights.

    QTensor (weight-only): contract against the raw int8 (converted to the
    activation dtype), then apply the per-output-channel scale, reordered
    and broadcast to the einsum's output labels. QTensorA8: additionally
    quantize the activations per-token over the contracted axes and run the
    contraction as int8 x int8 -> int32 on the MXU (see QTensorA8). Both
    require the quantization axes to be exactly the contracted weight axes —
    true for every QUANT_AXES entry and call site in models/ops.
    """
    if not isinstance(w, QTensor):
        return jnp.einsum(spec, x, w)
    ins, out = spec.split("->")
    xl, wl = ins.split(",")
    if isinstance(w, QTensorA8):
        cont_axes = tuple(i for i, c in enumerate(xl) if c in wl)
        x32 = x.astype(jnp.float32)
        amax = jnp.max(jnp.abs(x32), axis=cont_axes, keepdims=True)
        xs = jnp.where(amax > 0, amax / 127.0, 1.0)
        xq = jnp.clip(jnp.round(x32 / xs), -127, 127).astype(jnp.int8)
        acc = jnp.einsum(spec, xq, w.q,
                         preferred_element_type=jnp.int32)
        y = (acc.astype(jnp.float32)
             * _scale_to_out(xl, out, xs)
             * _scale_to_out(wl, out, w.scale))
        return y.astype(x.dtype)
    y = jnp.einsum(spec, x, w.q.astype(x.dtype))
    scale_t = _scale_to_out(wl, out, w.scale)
    return y * scale_t.astype(y.dtype)


def take_rows(w, ids: jax.Array, dtype) -> jax.Array:
    """Row lookup (embedding) honoring quantization: dequantize only the
    gathered rows."""
    if not isinstance(w, QTensor):
        return jnp.take(w, ids, axis=0).astype(dtype)
    rows = jnp.take(w.q, ids, axis=0).astype(dtype)
    scales = jnp.take(w.scale, ids, axis=0).astype(dtype)
    return rows * scales


def tied_head_einsum(x: jax.Array, embed) -> jax.Array:
    """Logits through the tied embedding: x [T, E] @ embed.T [E, V].

    Quantized embeddings route through `einsum` with the transposed spec —
    the per-row scales sit on the non-contracted V axis, so both the
    weight-only and W8A8 paths apply unchanged."""
    if not isinstance(embed, QTensor):
        return jnp.einsum("te,ev->tv", x, embed.T)
    return einsum("te,ve->tv", x, embed)


def param_bytes(params: Dict) -> int:
    """Total bytes of the (possibly quantized) parameter tree."""
    total = 0
    for leaf in jax.tree.leaves(params):
        total += leaf.size * leaf.dtype.itemsize
    return total
