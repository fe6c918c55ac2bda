"""Mamba-2 state-space mixer pieces: the depthwise causal conv with carried
rows, the chunked scan (SSD) with an initial state handed in and the final
state handed out, and the one-token update of a batch of states.

A sequence's state a layer is `S` [H, P, N] float32 (H heads of P lanes, N
state lanes a head; head h reads the B / C rows of group h // (H / G)) and
the conv's last K-1 input rows [K-1, C]. Neither grows with the context.

    S_t = exp(dt_t a) S_{t-1} + dt_t x_t (x) B_t      y_t = S_t C_t + D x_t

Plain XLA compositions (a kernel of its own is a later step). Every product
that touches the state runs in float32 at precision HIGHEST: the state is
what a 2,000-token prompt accumulates into, and a bf16 pass over it is the
"state kept in bf16" the tests refuse. Their FLOPs are small beside the
projections' (a 256-token chunk: 0.6 GFLOP a layer).

Rows that are padding (a prompt padded to its bucket, a chunk to 256, an
empty decode slot) are handed in with dt = 0: exp(0) = 1 and dt x = 0, so
they leave `S` bit for bit; the conv rows kept are the last K-1 REAL ones.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

_HI = jax.lax.Precision.HIGHEST


def conv_rows(xbc: jax.Array, prev: jax.Array, w: jax.Array, b: jax.Array,
              n_valid) -> tuple[jax.Array, jax.Array]:
    """Depthwise causal conv over time with bias, then silu.

    xbc [T, C] (the first `n_valid` rows are real), prev [K-1, C] the rows
    before xbc[0] (zeros at a sequence's start), w [K, C], b [C] ->
    (out [T, C], the last K-1 real rows seen [K-1, C]):
    out_t = silu(b + sum_k w_k * in_{t-(K-1)+k})."""
    k = w.shape[0]
    t = xbc.shape[0]
    cat = jnp.concatenate([prev.astype(xbc.dtype), xbc])  # [K-1+T, C]
    acc = b.astype(jnp.float32)
    for j in range(k):
        acc = acc + w[j].astype(jnp.float32) * cat[j:j + t].astype(
            jnp.float32)
    # real rows are cat[K-1 : K-1+n_valid]; the K-1 before the next token
    kept = jax.lax.dynamic_slice_in_dim(cat, n_valid, k - 1)
    return jax.nn.silu(acc).astype(xbc.dtype), kept.astype(prev.dtype)


def conv_step(xbc: jax.Array, prev: jax.Array, w: jax.Array, b: jax.Array,
              live: jax.Array) -> tuple[jax.Array, jax.Array]:
    """conv_rows for one token a slot: xbc [B, C], prev [B, K-1, C], live
    [B] bool -> (out [B, C], prev shifted by the token where live)."""
    cat = jnp.concatenate([prev.astype(xbc.dtype), xbc[:, None]], axis=1)
    acc = b.astype(jnp.float32) + jnp.einsum(
        "bkc,kc->bc", cat.astype(jnp.float32), w.astype(jnp.float32))
    kept = jnp.where(live[:, None, None], cat[:, 1:].astype(prev.dtype), prev)
    return jax.nn.silu(acc).astype(xbc.dtype), kept


def _by_head(a: jax.Array, heads: int) -> jax.Array:
    """[..., G, N] (a row a group) -> [..., H, N] (each head its group's)."""
    return jnp.repeat(a, heads // a.shape[-2], axis=-2)


def scan_chunked(x: jax.Array, dt: jax.Array, a: jax.Array, bm: jax.Array,
                 cm: jax.Array, d: jax.Array, init: jax.Array,
                 chunk: int) -> tuple[jax.Array, jax.Array]:
    """The recurrence over T tokens in chunks of `chunk` (T a multiple of
    min(chunk, T)): inside a chunk the quadratic form as matmuls, between
    chunks the state passed on.

    x [T, H, P], dt [T, H] float32 (softplus applied; 0 on padding rows),
    a [H] (negative), bm / cm [T, G, N], d [H], init [H, P, N] float32 ->
    (y [T, H, P] float32, final state [H, P, N] float32)."""
    t, h, p = x.shape
    q = min(chunk, t)
    if t % q:
        raise ValueError(f"{t} rows are no multiple of the scan chunk {q}")
    g, n = bm.shape[-2:]
    f32 = jnp.float32
    xs = x.astype(f32).reshape(t // q, q, h, p)
    dts = dt.astype(f32).reshape(t // q, q, h)
    bs = bm.astype(f32).reshape(t // q, q, g, n)
    cs = cm.astype(f32).reshape(t // q, q, g, n)
    causal = jnp.tril(jnp.ones((q, q), bool))

    def one(s_in, c):
        xc, dtc, bc, cc = c
        seg = jnp.cumsum(dtc * a.astype(f32), axis=0)  # [Q, H], <= 0
        # decay from token j to token i of the chunk (i >= j)
        decay = jnp.exp(jnp.where(causal[:, :, None],
                                  seg[:, None, :] - seg[None, :, :], -jnp.inf))
        scores = jnp.einsum("ign,jgn->ijg", cc, bc, precision=_HI)
        scores = jnp.repeat(scores, h // g, axis=-1) * decay  # [Q, Q, H]
        xdt = xc * dtc[..., None]  # [Q, H, P]
        y = jnp.einsum("ijh,jhp->ihp", scores, xdt, precision=_HI)
        # what the chunk found in the state
        y = y + jnp.exp(seg)[..., None] * jnp.einsum(
            "ihn,hpn->ihp", _by_head(cc, h), s_in, precision=_HI)
        tail = jnp.exp(seg[-1][None] - seg)  # [Q, H] decay to the chunk's end
        s_out = jnp.exp(seg[-1])[:, None, None] * s_in + jnp.einsum(
            "jhp,jhn->hpn", xdt * tail[..., None], _by_head(bc, h),
            precision=_HI)
        return s_out, y

    final, ys = jax.lax.scan(one, init.astype(f32), (xs, dts, bs, cs))
    y = ys.reshape(t, h, p) + d.astype(f32)[None, :, None] * x.astype(f32)
    return y, final


def step(x: jax.Array, dt: jax.Array, a: jax.Array, bm: jax.Array,
         cm: jax.Array, d: jax.Array,
         state: jax.Array) -> tuple[jax.Array, jax.Array]:
    """One token a slot: x [B, H, P], dt [B, H] float32 (0 on an empty
    slot), bm / cm [B, G, N], state [B, H, P, N] float32 ->
    (y [B, H, P] float32, the states after the token)."""
    f32 = jnp.float32
    h = x.shape[1]
    xf, dt = x.astype(f32), dt.astype(f32)
    decay = jnp.exp(dt * a.astype(f32))  # [B, H]
    new = (decay[..., None, None] * state
           + (xf * dt[..., None])[..., None]
           * _by_head(bm.astype(f32), h)[:, :, None, :])
    y = jnp.sum(new * _by_head(cm.astype(f32), h)[:, :, None, :], axis=-1)
    return y + d.astype(f32)[None, :, None] * xf, new


def gate_norm(y: jax.Array, z: jax.Array, w: jax.Array, groups: int,
              eps: float) -> jax.Array:
    """Gate first, then an RMS norm by group: w * rms_g(y * silu(z)), the
    mean over each of `groups` runs of lanes. y, z [T, d_in] -> [T, d_in]
    float32."""
    v = y.astype(jnp.float32) * jax.nn.silu(z.astype(jnp.float32))
    t, width = v.shape
    vg = v.reshape(t, groups, width // groups)
    vg = vg * jax.lax.rsqrt(jnp.mean(vg * vg, axis=-1, keepdims=True) + eps)
    return vg.reshape(t, width) * w.astype(jnp.float32)
