"""LFM2's gated short convolution between its two projections:

    [B | C | u] = W_in h      g_t = B_t * u_t
    c_t = sum_k w_k * g_{t-(K-1)+k}        (depthwise, causal, g_{<0} = 0)
    y_t = W_out (C_t * c_t)

No bias, no activation, no recurrence: a sequence's whole state a layer is
the last K-1 rows of g, [K-1, E] in the model's dtype. The projections are
the caller's (`models/llama._conv_operator`); here are the two gates and the
taps, as plain XLA compositions: elementwise over [rows, E], a step's work is
36 KB a live row a layer, and what it costs is launches, so both forms are
written to fuse into one or two loops. The tap loop is Mamba-2's
(`ops/ssm.tap_sum`); the bias and the silu around it there are not.

g is rounded to the model's dtype BEFORE the taps in both forms, because that
is what the state keeps: a chunk boundary, or a token decoded through the
slot, then meets the same numbers a whole prompt met.

Padding rows (a chunk padded to 256) leave the state as the last REAL row
left it; an empty decode slot's rows come back bit for bit.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from dynamo_tpu.ops.ssm import tap_sum


def _split(bcu: jax.Array):
    e = bcu.shape[-1] // 3
    return bcu[..., :e], bcu[..., e:2 * e], bcu[..., 2 * e:]


def gated_rows(bcu: jax.Array, prev: jax.Array, w: jax.Array,
               n_valid) -> tuple[jax.Array, jax.Array]:
    """The rows of one prompt (or one chunk of it). bcu [T, 3E] = [B | C |
    u] (the first `n_valid` rows real), prev [K-1, E] the rows of g before
    bcu[0] (zeros at a sequence's start), w [K, E] -> (C * conv(B * u)
    [T, E], the last K-1 real rows of g [K-1, E])."""
    k, t = w.shape[0], bcu.shape[0]
    b, c, u = _split(bcu)
    g = b * u
    cat = jnp.concatenate([prev.astype(g.dtype), g])  # [K-1+T, E]
    y = c.astype(jnp.float32) * tap_sum(0.0, cat, w, t)
    # real rows are cat[K-1 : K-1+n_valid]; the K-1 before the next token
    kept = jax.lax.dynamic_slice_in_dim(cat, n_valid, k - 1)
    return y.astype(bcu.dtype), kept.astype(prev.dtype)


def gated_step(bcu: jax.Array, prev: jax.Array, w: jax.Array,
               live: jax.Array) -> tuple[jax.Array, jax.Array]:
    """gated_rows for one token a slot: bcu [B, 3E], prev [B, K-1, E], live
    [B] bool -> (out [B, E], prev shifted by the token's g where live)."""
    b, c, u = _split(bcu)
    g = b * u
    cat = jnp.concatenate([prev.astype(g.dtype), g[:, None]], axis=1)
    acc = jnp.sum(cat.astype(jnp.float32) * w.astype(jnp.float32), axis=1)
    kept = jnp.where(live[:, None, None], cat[:, 1:].astype(prev.dtype), prev)
    return (c.astype(jnp.float32) * acc).astype(bcu.dtype), kept
