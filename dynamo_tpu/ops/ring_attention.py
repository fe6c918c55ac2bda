"""Ring attention + Ulysses sequence parallelism for long-context prefill.

The reference stack has no sequence-length scaling story at all (SURVEY.md §5:
long-context is entirely inside the consumed engines); this module is the
beyond-parity extension that makes >100k-token prefill first-class on TPU.
Two interchangeable strategies, both expressed as shard_map collectives over a
`seq` mesh axis laid out on the ICI torus:

- **Ring attention** (`ring_prefill_attention`): K/V chunks rotate around the
  ring via `lax.ppermute` while each device keeps its Q chunk and accumulates
  an online-softmax (flash) state. Communication is nearest-neighbour on ICI
  and overlaps with the block matmuls under XLA's async collective scheduling.
  Memory per device is O(S/sp * S_chunk) — no device ever sees the full
  attention matrix.
- **Ulysses** (`ulysses_prefill_attention`): two `lax.all_to_all`s re-shard
  [seq/sp, H] -> [seq, H/sp], run dense local attention over the full
  sequence with 1/sp of the heads, and shard back. Cheaper collectives for
  moderate sp (all-to-all rides ICI), but requires num_kv_heads % sp == 0.

Both compose with tensor parallelism: run under a ("seq", "model") mesh with
heads sharded on `model` — attention is head-parallel, so the two axes never
interact. Layouts match `dynamo_tpu.ops.attention.prefill_attention`:
q [S, H, D], k/v [S, KV, D], one (padded) sequence, causal + seq_len mask.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P

from dynamo_tpu.ops.attention import repeat_kv

_NEG = -1e30  # finite mask value: keeps online-softmax max/exp NaN-free


def _online_block_update(o, m, l, q_scaled, k, v, mask):
    """One flash-attention block: returns updated (o, m, l).

    q_scaled [Sq, H, D]; k/v [Sk, H, D]; mask [Sq, Sk] bool (True = attend);
    o [H, Sq, D] f32; m, l [H, Sq] f32.
    """
    s = jnp.einsum(
        "qhd,khd->hqk", q_scaled, k, preferred_element_type=jnp.float32
    )
    s = jnp.where(mask[None], s, _NEG)
    m_new = jnp.maximum(m, s.max(axis=-1))
    p = jnp.exp(s - m_new[..., None])
    p = jnp.where(mask[None], p, 0.0)  # rows with no valid key stay exactly 0
    alpha = jnp.exp(m - m_new)
    l_new = l * alpha + p.sum(axis=-1)
    o_new = o * alpha[..., None] + jnp.einsum(
        "hqk,khd->hqd", p, v.astype(jnp.float32)
    )
    return o_new, m_new, l_new


def _ring_attention_local(
    q: jax.Array,  # [Sq, H, D] local Q chunk
    k: jax.Array,  # [Sk, KV, D] local K chunk
    v: jax.Array,
    seq_len: jax.Array,  # scalar int32: true global length (rest is padding)
    *,
    axis_name: str,
    causal: bool,
) -> jax.Array:
    axis_size = lax.psum(1, axis_name)
    idx = lax.axis_index(axis_name)
    sq, n_heads, head_dim = q.shape
    sk, n_kv, _ = k.shape
    group = n_heads // n_kv

    scale = 1.0 / jnp.sqrt(jnp.asarray(head_dim, jnp.float32))
    qf = (q.astype(jnp.float32) * scale).astype(q.dtype)
    q_pos = idx * sq + jnp.arange(sq)

    o0 = jnp.zeros((n_heads, sq, head_dim), jnp.float32)
    m0 = jnp.full((n_heads, sq), _NEG, jnp.float32)
    l0 = jnp.zeros((n_heads, sq), jnp.float32)
    perm = [(j, (j + 1) % axis_size) for j in range(axis_size)]

    def step(i, carry):
        o, m, l, k, v = carry
        # after i rotations we hold the chunk that originated on device idx-i
        src = (idx - i) % axis_size
        k_pos = src * sk + jnp.arange(sk)
        mask = (k_pos < seq_len)[None, :]
        if causal:
            mask = mask & (k_pos[None, :] <= q_pos[:, None])
        else:
            mask = jnp.broadcast_to(mask, (sq, sk))
        kk = repeat_kv(k, group, axis=1)
        vv = repeat_kv(v, group, axis=1)
        o, m, l = _online_block_update(o, m, l, qf, kk, vv, mask)
        # rotate K/V to the next ring neighbour (nearest-neighbour on ICI)
        k = lax.ppermute(k, axis_name, perm)
        v = lax.ppermute(v, axis_name, perm)
        return o, m, l, k, v

    o, m, l, _, _ = lax.fori_loop(0, axis_size, step, (o0, m0, l0, k, v))
    out = o / jnp.maximum(l, 1e-20)[..., None]
    return jnp.transpose(out, (1, 0, 2)).astype(q.dtype)  # [Sq, H, D]


def _head_axis(mesh: Mesh, head_axis: Optional[str]) -> Optional[str]:
    if head_axis is not None and head_axis in mesh.axis_names:
        return head_axis
    return None


def ring_prefill_attention(
    q: jax.Array,  # [S, H, D] global (sharded on seq_axis by caller or here)
    k: jax.Array,  # [S, KV, D]
    v: jax.Array,
    seq_len,  # int or scalar array: true (unpadded) length
    mesh: Mesh,
    *,
    seq_axis: str = "seq",
    head_axis: Optional[str] = "model",
    causal: bool = True,
) -> jax.Array:
    """Causal flash attention with the sequence sharded over `seq_axis`.

    S must divide evenly by the `seq_axis` size (pad to a multiple; padding
    beyond `seq_len` is masked). Heads additionally shard over `head_axis`
    when that axis exists in the mesh (tensor parallel).
    """
    ha = _head_axis(mesh, head_axis)
    fn = functools.partial(
        _ring_attention_local, axis_name=seq_axis, causal=causal
    )
    return jax.shard_map(
        fn,
        mesh=mesh,
        in_specs=(
            P(seq_axis, ha, None),
            P(seq_axis, ha, None),
            P(seq_axis, ha, None),
            P(),
        ),
        out_specs=P(seq_axis, ha, None),
        check_vma=False,
    )(q, k, v, jnp.asarray(seq_len, jnp.int32))


# ---------------------------------------------------------------- Ulysses --


def _ulysses_local(
    q: jax.Array,  # [Sq, H, D] seq-sharded chunk
    k: jax.Array,  # [Sq, KV, D]
    v: jax.Array,
    seq_len: jax.Array,
    *,
    axis_name: str,
    causal: bool,
) -> jax.Array:
    sp = lax.psum(1, axis_name)
    n_heads, n_kv = q.shape[1], k.shape[1]
    if n_kv % sp != 0:
        # not enough KV heads to scatter: replicate them up to the Q heads
        k = repeat_kv(k, n_heads // n_kv, axis=1)
        v = repeat_kv(v, n_heads // n_kv, axis=1)
    # [S/sp, H, D] -> [S, H/sp, D]: scatter heads, gather sequence
    q = lax.all_to_all(q, axis_name, split_axis=1, concat_axis=0, tiled=True)
    k = lax.all_to_all(k, axis_name, split_axis=1, concat_axis=0, tiled=True)
    v = lax.all_to_all(v, axis_name, split_axis=1, concat_axis=0, tiled=True)

    s, h_local, head_dim = q.shape
    group = h_local // k.shape[1]
    kk = repeat_kv(k, group, axis=1)
    vv = repeat_kv(v, group, axis=1)
    scale = 1.0 / jnp.sqrt(jnp.asarray(head_dim, jnp.float32))
    qf = (q.astype(jnp.float32) * scale).astype(q.dtype)
    pos = jnp.arange(s)
    mask = (pos[None, :] < seq_len)
    if causal:
        mask = mask & (pos[None, :] <= pos[:, None])
    else:
        mask = jnp.broadcast_to(mask, (s, s))
    o = jnp.zeros((h_local, s, head_dim), jnp.float32)
    m = jnp.full((h_local, s), _NEG, jnp.float32)
    l = jnp.zeros((h_local, s), jnp.float32)
    o, m, l = _online_block_update(o, m, l, qf, kk, vv, mask)
    out = (o / jnp.maximum(l, 1e-20)[..., None]).astype(q.dtype)
    out = jnp.transpose(out, (1, 0, 2))  # [S, H/sp, D]
    # [S, H/sp, D] -> [S/sp, H, D]: gather heads, scatter sequence back
    return lax.all_to_all(out, axis_name, split_axis=0, concat_axis=1, tiled=True)


def ulysses_prefill_attention(
    q: jax.Array,  # [S, H, D]
    k: jax.Array,  # [S, KV, D]
    v: jax.Array,
    seq_len,
    mesh: Mesh,
    *,
    seq_axis: str = "seq",
    head_axis: Optional[str] = "model",
    causal: bool = True,
) -> jax.Array:
    """All-to-all (DeepSpeed-Ulysses-style) sequence-parallel attention.

    Requires (local) head count divisible by the seq axis size after GQA
    replication. Better collective efficiency than the ring at moderate sp;
    the ring wins at large sp / very long S (nearest-neighbour only).
    """
    ha = _head_axis(mesh, head_axis)
    fn = functools.partial(_ulysses_local, axis_name=seq_axis, causal=causal)
    return jax.shard_map(
        fn,
        mesh=mesh,
        in_specs=(
            P(seq_axis, ha, None),
            P(seq_axis, ha, None),
            P(seq_axis, ha, None),
            P(),
        ),
        out_specs=P(seq_axis, ha, None),
        check_vma=False,
    )(q, k, v, jnp.asarray(seq_len, jnp.int32))
