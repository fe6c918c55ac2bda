#!/usr/bin/env python3
"""The Mamba-2 one-token state update measured alone on the chip, before the
cell: one layer's update at the Nemotron cell's shapes (64 slots, a state
[64 heads, 64, 128] float32 each = 2.1 MB) with 0 / 8 / 16 / 27 / 48 / 64
slots live, the kernel over the live slots (`ops/ssm.update_live`, at a few
head blocks) against the XLA pass over every slot (`ops/ssm.step_every_slot`:
dt = 0 on the empty ones).

Each timing is one program of 16 updates in a `lax.scan` with the state as
the donated carry (a decode window's form; the next token's x hangs on the
last y, so nothing is hoisted out of the loop), timed in 5 groups of 4
calls of which the median is kept (the host pauses now and then); the first
call's results are held against the XLA pass: live rows' y and states
within float32 rounding, empty slots' states bit for bit, empty rows' y 0.

    python scripts/ssm_microbench.py   ->  chiprun_out/ssm-microbench.json

SSM_MICROBENCH_SHAPE=slots,heads,head_dim,groups,state shrinks it for a CPU
rehearsal (the kernel then runs in interpret mode), or sets another model's
extents (PR 44: 64,32,128,2,256 is Falcon-H1's, a head's state 128 KB);
SSM_MICROBENCH_LIVE=0,8,16,32,48,64 the live counts; SSM_MICROBENCH_OUT the
record's name. With SSM_MICROBENCH_POOL=<layers> the kernel also runs over a
POOL of states [layers x slots, ...] under a `base` of one layer's offset, as
a model whose layers run as one scan hands it (models/llama._parallel_layers):
its rows against the plain kernel's bit for bit, every other layer's rows
untouched, and its time.
"""
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np

from dynamo_tpu.ops import ssm

B, H, P, G, N = (int(x) for x in os.environ.get(
    "SSM_MICROBENCH_SHAPE", "64,64,64,8,128").split(","))
LIVE = tuple(int(x) for x in os.environ.get(
    "SSM_MICROBENCH_LIVE", "0,8,16,27,48,64").split(","))
POOL = int(os.environ.get("SSM_MICROBENCH_POOL", "0"))
OUT = os.environ.get("SSM_MICROBENCH_OUT", "ssm-microbench.json")
STEPS, CALLS, GROUPS = 16, 4, 5


def window(update):
    """16 updates, the state carried and donated."""
    def run(state, x, dt, a, bm, cm, d, live):
        slots = ssm.live_slots(live)

        def body(carry, _):
            st, y = carry
            y, st = update(x + 1e-3 * y, dt, a, bm, cm, d, st, live, slots)
            return (st, y), None

        (state, y), _ = jax.lax.scan(
            body, (state, jnp.zeros(x.shape, jnp.float32)), None,
            length=STEPS)
        return state, y
    return jax.jit(run, donate_argnums=(0,))


def xla_update(x, dt, a, bm, cm, d, st, live, slots):
    return ssm.step_every_slot(x, dt, a, bm, cm, d, st, live)


def us_an_update(fn, state0, args) -> float:
    """Median over GROUPS timings of CALLS calls of `fn` (STEPS updates
    each), the state carried from call to call; microseconds an update."""
    state, y = fn(jnp.asarray(state0), *args)
    jax.block_until_ready((state, y))
    groups = []
    for _ in range(GROUPS):
        t0 = time.perf_counter()
        for _ in range(CALLS):
            state, y = fn(state, *args)
        jax.block_until_ready((state, y))
        groups.append((time.perf_counter() - t0) / (CALLS * STEPS) * 1e6)
    return sorted(groups)[GROUPS // 2]


def main():
    dev = jax.devices()[0]
    on_chip = dev.platform == "tpu"
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(size=(B, H, P)), jnp.bfloat16)
    dt = jnp.asarray(rng.uniform(1e-3, 1e-1, (B, H)), jnp.float32)
    a = -jnp.asarray(rng.uniform(1, 16, (H,)), jnp.float32)
    bm = jnp.asarray(rng.normal(size=(B, G, N)), jnp.bfloat16)
    cm = jnp.asarray(rng.normal(size=(B, G, N)), jnp.bfloat16)
    d = jnp.asarray(rng.normal(size=(H,)), jnp.float32)
    state0 = rng.normal(size=(B, H, P, N)).astype(np.float32)
    slot_bytes = 2 * H * P * N * 4

    forms = {"xla_all_slots": window(xla_update)}
    blocks = sorted({hb for hb in (H // 4, H // 2, H) if hb and H % hb == 0
                     and hb * P * N * 4 <= ssm._STATE_BLOCK_BYTES})
    for hb in blocks:
        def kernel(*args, hb=hb):
            return ssm.update_live(*args, interpret=not on_chip,
                                   head_block=hb)
        forms[f"kernel_live_slots_hb{hb}"] = window(kernel)
    if POOL:
        # layer 1 of a pool of POOL layers' states, under its offset
        def pooled(*args):
            return ssm.update_live(*args, base=jnp.int32(B),
                                   interpret=not on_chip)
        pool_fn = window(pooled)

    rec = {"device": {"platform": dev.platform, "kind": dev.device_kind},
           "shapes": {"slots": B, "heads": H, "head_dim": P, "groups": G,
                      "state": N, "steps_a_call": STEPS,
                      "calls_a_timing": CALLS, "timings": GROUPS,
                      "slot_bytes_in_and_out": slot_bytes,
                      "default_head_block": ssm._head_block(H, P * N * 4)},
           "us_an_update": {}, "gb_per_s_of_live_bytes": {}, "against_xla": {}}
    counts = [c for c in LIVE if c <= B]
    for count in counts:
        mask = np.zeros((B,), bool)
        mask[rng.permutation(B)[:count]] = True
        live = jnp.asarray(mask)
        args = (x, dt, a, bm, cm, d, live)
        want_state, want_y = (np.asarray(v) for v in forms["xla_all_slots"](
            jnp.asarray(state0), *args))
        for name, fn in forms.items():
            got_state, got_y = fn(jnp.asarray(state0), *args)
            got_state, got_y = np.asarray(got_state), np.asarray(got_y)
            if name != "xla_all_slots":
                scale = np.abs(want_state[mask]).max() if count else 1.0
                rec["against_xla"][f"{name}.live{count}"] = {
                    "state_rel": float(np.abs(
                        got_state[mask] - want_state[mask]).max() / scale
                    ) if count else 0.0,
                    "y_rel": float(np.abs(got_y[mask] - want_y[mask]).max()
                                   / np.abs(want_y[mask]).max()
                                   ) if count else 0.0,
                    "empty_states_bit_for_bit": bool(
                        (got_state[~mask] == state0[~mask]).all()),
                    "empty_rows_y_zero": bool((got_y[~mask] == 0).all()),
                }
            us = us_an_update(fn, state0, args)
            rec["us_an_update"][f"{name}.live{count}"] = round(us, 2)
            if count:
                rec["gb_per_s_of_live_bytes"][f"{name}.live{count}"] = round(
                    count * slot_bytes / us / 1e3, 1)
        if POOL:
            pool0 = np.tile(state0, (POOL, 1, 1, 1))
            pool0[:B] *= 0.5  # the other layers' rows differ from layer 1's
            got_pool, got_y = pool_fn(jnp.asarray(pool0), *args)
            got_pool = np.asarray(got_pool)
            plain_state, plain_y = (np.asarray(v) for v in forms[
                f"kernel_live_slots_hb{ssm._head_block(H, P * N * 4)}"](
                    jnp.asarray(state0), *args))
            others = np.ones((POOL * B,), bool)
            others[B:2 * B] = False
            rec["against_xla"][f"kernel_pool_base.live{count}"] = {
                "state_rel": 0.0 if (got_pool[B:2 * B] == plain_state).all()
                else 1.0,
                "y_rel": 0.0 if (np.asarray(got_y) == plain_y).all() else 1.0,
                "empty_states_bit_for_bit": bool(
                    (got_pool[others] == pool0[others]).all()),
                "empty_rows_y_zero": bool((np.asarray(got_y)[~mask]
                                           == 0).all())}
            del got_pool
            rec["us_an_update"][f"kernel_pool_base.live{count}"] = round(
                us_an_update(pool_fn, pool0, args), 2)
    os.makedirs("chiprun_out", exist_ok=True)
    with open(os.path.join("chiprun_out", OUT), "w") as f:
        json.dump(rec, f, indent=1)
    print(json.dumps(rec, indent=1))
    bad = [k for k, v in rec["against_xla"].items()
           if not (v["empty_states_bit_for_bit"] and v["empty_rows_y_zero"]
                   and v["state_rel"] < 1e-5 and v["y_rel"] < 1e-5)]
    if bad:
        print("NOT the XLA pass's results:", bad)
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
