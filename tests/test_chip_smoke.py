"""What stands between the program and a silent CPU/XLA run: the one chip
table, the counted Pallas->XLA demotions, and chip_smoke.py's own rules
(the parent leaves JAX alone; the default invocation finds a TPU or fails;
the CPU rehearsal can never print the pass line)."""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SMOKE = os.path.join(REPO, "chip_smoke.py")


# ------------------------------------------------------- the chip table --


def test_device_kind_strings_map_onto_the_one_table():
    from dynamo_tpu.profiler import systems

    # a v5e reports itself as "TPU v5 lite" — not as anything with "v5e"
    assert systems.chip_for_device_kind("TPU v5 lite") is systems.CHIPS["v5e"]
    assert systems.require_chip("TPU v5 lite").bf16_flops == 197e12
    assert systems.require_chip("TPU v5 lite").hbm_bw == 8.19e11
    assert systems.chip_for_device_kind("TPU v6 lite").name == "v6e"
    assert systems.chip_for_device_kind("TPU v5p").name == "v5p"
    assert systems.chip_for_device_kind("cpu") is None


def test_unknown_tpu_is_an_error_where_a_peak_is_needed(monkeypatch):
    from dynamo_tpu.exporter import tpu_exporter
    from dynamo_tpu.kvbm import cost_model
    from dynamo_tpu.profiler import systems

    assert systems.chip_for_device_kind("TPU v9 ultra") is None
    with pytest.raises(KeyError, match="not in the chip table"):
        systems.require_chip("TPU v9 ultra")

    class Dev:
        platform, device_kind, id = "tpu", "TPU v9 ultra", 0

    import jax

    monkeypatch.setattr(jax, "devices", lambda *a: [Dev()])
    with pytest.raises(KeyError):  # the KVBM gate needs a FLOP/s peak
        cost_model._detect_chip_flops()
    # the exporter labels it by its own name and models no power for it
    assert tpu_exporter._chip_of(Dev()) == ("tpu v9 ultra", None)
    Dev.device_kind = "TPU v5 lite"
    assert tpu_exporter._chip_of(Dev()) == ("v5e", 170.0)
    assert cost_model._detect_chip_flops() == 197e12


# ------------------------------------------- counted Pallas->XLA routes --


def _pools(n_kv, d, quantized=False, pages=8, ps=4):
    import jax.numpy as jnp

    from dynamo_tpu.ops import attention as att

    rng = np.random.default_rng(0)
    kf = rng.normal(size=(pages * ps, n_kv, d)).astype(np.float32)
    if quantized:
        w = att.kv_lane_width(n_kv, d, True)
        kp = att.pack_kv_rows(jnp.asarray(kf), w).reshape(pages, ps, w)
    else:
        kp = jnp.asarray(kf.reshape(pages, ps, n_kv * d))
    return kp, kp


def _route_calls():
    """{(op, reason): thunk} — one call per route by which an op that
    `auto` resolved to a Pallas kernel still ends on the XLA path."""
    import jax.numpy as jnp

    from dynamo_tpu.ops import attention as att

    ps, h, n_kv, d = 4, 4, 2, 64  # KV*D = 128: passes the lane gate
    rng = np.random.default_rng(1)
    q1 = jnp.asarray(rng.normal(size=(2, h, d)), jnp.float32)
    qc = jnp.asarray(rng.normal(size=(8, h, d)), jnp.float32)
    kp, vp = _pools(n_kv, d)
    k8, v8 = _pools(n_kv, d, quantized=True)
    bt = jnp.asarray([[1, 2], [3, 0]], jnp.int32)
    cl = jnp.asarray([6, 3], jnp.int32)
    pages = jnp.asarray([4, 5, 6, 0], jnp.int32)
    win = jnp.asarray(4, jnp.int32)
    kw = dict(page_size=ps, num_kv_heads=n_kv)
    k_small = _pools(2, 32)[0]  # KV*D = 64: below the 128-lane tile

    def prefill(dd=d, **k):
        x = jnp.asarray(rng.normal(size=(8, h, dd)), jnp.float32)
        kv = jnp.asarray(rng.normal(size=(8, n_kv, dd)), jnp.float32)
        return att.prefill_attention(x, kv, kv, 8, **k)

    q_small = jnp.asarray(rng.normal(size=(2, h, 32)), jnp.float32)
    return {
        ("decode", "window_softcap"): lambda: att.paged_attention_decode(
            q1, kp, vp, bt, cl, window=win, **kw),
        ("decode", "lane_gate"): lambda: att.paged_attention_decode(
            q_small, k_small, k_small, bt, cl, page_size=ps,
            num_kv_heads=2),
        ("prefill", "window_softcap"): lambda: prefill(window=win),
        ("prefill", "head_dim"): lambda: prefill(dd=48),
        ("chunk attention", "window_softcap"): lambda: att.chunk_attention(
            qc, kp, vp, pages, 4, logit_cap=30.0, **kw),
        ("chunk attention", "int8_not_validated"):
            lambda: att.chunk_attention(qc, k8, v8, pages, 4, **kw),
        ("ragged attention", "not_validated"):
            lambda: att.ragged_mixed_attention(
                jnp.concatenate([q1, qc]), kp, vp, bt, cl, pages, 4,
                num_decode=2, **kw),
        ("ragged attention", "window_softcap"):
            lambda: att.ragged_verify_attention(
                jnp.concatenate([q1, q1, qc]), kp, vp, bt, cl - 2, pages, 4,
                num_verify=2, verify_width=2, window=win, **kw),
    }


@pytest.mark.parametrize("route", sorted(_route_calls()))
def test_every_auto_to_xla_route_is_counted(monkeypatch, route):
    """On a TPU `auto` resolves to the kernels; each gate that then sends
    an op to XLA must leave a count behind (dynamo_pallas_fallback_total),
    the hardware-validation flags and window/soft-cap demotions included."""
    import jax

    from dynamo_tpu.ops import attention as att
    from dynamo_tpu.ops import ragged_attention as ra

    for var in ("DYNAMO_TPU_ATTN_BACKEND", "DYNAMO_TPU_CHUNK_ATTENTION",
                "DYNAMO_TPU_RAGGED_ATTENTION"):
        monkeypatch.delenv(var, raising=False)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    # the ragged kernel is the default since PR 26: its gate is a route
    # only when the flag is pulled, and every other route lies past it
    assert ra.RAGGED_KERNEL_HW_VALIDATED is True
    if route == ("ragged attention", "not_validated"):
        monkeypatch.setattr(ra, "RAGGED_KERNEL_HW_VALIDATED", False)
    before = att.pallas_fallback_counts().get(route, 0)
    out = _route_calls()[route]()
    assert np.all(np.isfinite(np.asarray(out)))
    assert att.pallas_fallback_counts().get(route, 0) == before + 1
    op = route[0]
    assert att.attention_impl_counts().get((op, "xla"), 0) >= 1


def test_xla_by_choice_is_not_a_demotion():
    """The same calls on a backend that resolved to XLA in the first place
    (CPU tests, --attention-backend xla) count nothing; and `auto` never
    reaches the interpreter — only its name does."""
    from dynamo_tpu.ops import attention as att

    before = att.pallas_fallback_counts()
    with att.attention_context("xla", None):
        for thunk in _route_calls().values():
            thunk()
    assert att.pallas_fallback_counts() == before
    assert att._resolve_backend() == "xla"  # auto on this CPU


# ------------------------------------------------------- chip_smoke.py --


def _run_smoke(*argv, env_extra=None, timeout=600):
    env = dict(os.environ)
    env.update(env_extra or {})
    return subprocess.run([sys.executable, SMOKE, *argv], cwd=REPO, env=env,
                          capture_output=True, text=True, timeout=timeout)


def test_smoke_parent_never_imports_jax():
    """A parent that has touched JAX holds the chip its worker needs."""
    code = ("import sys; sys.argv=['chip_smoke.py','--help']\n"
            "import runpy\n"
            "try:\n"
            "    runpy.run_path(%r, run_name='__main__')\n"
            "except SystemExit:\n"
            "    pass\n"
            "import chip_smoke\n"
            "chip_smoke._cache_dir()\n"
            "from dynamo_tpu.profiler.systems import chip_for_device_kind\n"
            "assert 'jax' not in sys.modules and 'jaxlib' not in sys.modules"
            % SMOKE)
    r = subprocess.run([sys.executable, "-c", code], cwd=REPO,
                       capture_output=True, text=True, timeout=60)
    assert r.returncode == 0, r.stderr[-2000:]
    with open(SMOKE) as f:
        src = f.read()
    assert "import jax" not in src.replace("imports jax", "")
    assert "\"jax\" not in sys.modules" in src  # asserted at exit too


def test_smoke_default_invocation_fails_without_a_tpu():
    """This sandbox exports JAX_PLATFORMS=cpu; the children are told
    tpu,cpu, so the smoke cannot pass on the CPU by inheritance: non-zero
    exit, the worker's reason, and no result line."""
    r = _run_smoke(env_extra={"JAX_PLATFORMS": "cpu"}, timeout=300)
    assert r.returncode != 0
    assert "FAILED" in r.stdout
    assert "backend" in r.stdout and "tpu" in r.stdout
    assert '"ok"' not in r.stdout


@pytest.mark.slow
def test_smoke_cpu_rehearsal_passes_but_never_prints_the_pass_line():
    r = _run_smoke("--rehearse-cpu", timeout=900)
    assert r.returncode == 0, r.stdout[-3000:] + r.stderr[-2000:]
    lines = r.stdout.strip().splitlines()
    last = json.loads(lines[-1])
    assert last["rehearsal"] == "passed" and "ok" not in last
    assert last["device"]["platform"] == "cpu"
    summary = json.loads(lines[-2])["summary"]
    assert summary["serving"]["requests_failed"] == 0
    assert summary["serving"]["programs_after"] == summary["setup"]["programs"]
    assert summary["health"]["state"] == "healthy"
