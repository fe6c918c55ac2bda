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


def _pools(n_kv, d, quantized=False, pages=8, ps=4, lane_blocks=1):
    import jax.numpy as jnp

    from dynamo_tpu.ops import attention as att

    rng = np.random.default_rng(0)
    kf = rng.normal(size=(pages * ps, n_kv, d)).astype(np.float32)
    if quantized:
        w = att.kv_lane_width(n_kv, d, True, lane_blocks)
        kp = att.pack_kv_rows(jnp.asarray(kf), w, lane_blocks).reshape(
            pages, ps, w)
    else:
        kp = jnp.asarray(kf.reshape(pages, ps, n_kv * d))
    return kp, kp


def _mesh(**axes):
    import jax
    from jax.sharding import Mesh

    n = int(np.prod(list(axes.values())))
    return Mesh(np.array(jax.devices()[:n]).reshape(tuple(axes.values())),
                tuple(axes))


# the counters' name for each op of the table below
_COUNTED_AS = {"ragged mixed": "ragged attention",
               "ragged verify": "ragged attention"}
# the kernel entry point each op calls (module, attribute)
_KERNEL_OF = {"decode": ("pallas_attention", "paged_attention_decode"),
              "prefill": ("pallas_attention", "prefill_attention"),
              "chunk attention": ("pallas_attention",
                                  "chunk_prefill_attention"),
              "ragged attention": ("ragged_attention",
                                   "ragged_paged_attention")}


def _route_calls():
    """{(op, condition): (thunk, scope, ends_on, counted)}: one call for
    every way an attention op can be routed. `scope` is the
    `attention_context` the call runs in (backend, mesh, int8 lane blocks;
    `auto` resolves to the kernels, the test says the platform is a TPU),
    `ends_on` the (counters' op, implementation) the trace is noted with,
    `counted` the reasons `dynamo_pallas_fallback_total` gains one of."""
    import jax.numpy as jnp

    from dynamo_tpu.ops import attention as att

    ps, h, n_kv, d = 4, 4, 2, 64  # KV*D = 128: passes the lane gate
    rng = np.random.default_rng(1)
    q1 = jnp.asarray(rng.normal(size=(2, h, d)), jnp.float32)
    qc = jnp.asarray(rng.normal(size=(8, h, d)), jnp.float32)
    kp, vp = _pools(n_kv, d)
    k8, v8 = _pools(n_kv, d, quantized=True)
    k82, v82 = _pools(n_kv, d, quantized=True, lane_blocks=2)
    bt = jnp.asarray([[1, 2], [3, 0]], jnp.int32)
    cl = jnp.asarray([6, 3], jnp.int32)
    pages = jnp.asarray([4, 5, 6, 0], jnp.int32)
    win = jnp.asarray(4, jnp.int32)
    kw = dict(page_size=ps, num_kv_heads=n_kv)
    k_small = _pools(2, 32)[0]  # KV*D = 64: below the 128-lane tile
    q_small = jnp.asarray(rng.normal(size=(2, h, 32)), jnp.float32)
    qc_small = jnp.asarray(rng.normal(size=(8, h, 32)), jnp.float32)

    def decode(q=q1, k=kp, v=vp, **k_):
        return att.paged_attention_decode(q, k, v, bt, cl, **{**kw, **k_})

    def prefill(dd=d, **k):
        x = jnp.asarray(rng.normal(size=(8, h, dd)), jnp.float32)
        kv = jnp.asarray(rng.normal(size=(8, n_kv, dd)), jnp.float32)
        return att.prefill_attention(x, kv, kv, 8, **k)

    def chunk(q=qc, k=kp, v=vp, **k_):
        return att.chunk_attention(q, k, v, pages, 4, **{**kw, **k_})

    def mixed(qd=q1, qp=qc, k=kp, v=vp, **k_):
        return att.ragged_mixed_attention(
            jnp.concatenate([qd, qp]), k, v, bt, cl, pages, 4,
            num_decode=2, **{**kw, **k_})

    def verify(qd=q1, qp=qc, k=kp, v=vp, **k_):
        return att.ragged_verify_attention(
            jnp.concatenate([qd, qd, qp]), k, v, bt, cl - 2, pages, 4,
            num_verify=2, verify_width=2, **{**kw, **k_})

    auto = ("auto", None, 1)
    tp2 = ("auto", _mesh(data=1, model=2), 1)
    # tp=4 divides neither the 2 KV heads nor, so, the op
    tp4 = ("auto", _mesh(data=1, model=4), 1)
    seq = ("auto", _mesh(seq=2, model=1), 1)
    table = {
        # decode: a mesh that cannot split the op is dropped, the kernel
        # stays (GSPMD places it); only shapes send it to XLA
        ("decode", "head_gate"): (decode, tp4, "pallas", ["head_gate"]),
        # ... and the XLA twin runs under the same shard_map as the
        # kernel, so the mesh's gate is evaluated (and counted) for it too
        ("decode", "head_gate_under_xla"): (
            decode, ("xla", tp4[1], 1), "xla", ["head_gate"]),
        ("decode", "int8_blocks_indivisible"): (
            lambda: decode(k=k8, v=v8), tp2, "pallas", []),
        ("decode", "lane_gate"): (
            lambda: decode(q_small, k_small, k_small), auto, "xla",
            ["lane_gate"]),
        ("decode", "int8_lane_blocks"): (
            lambda: decode(k=k82, v=v82), ("auto", None, 2), "xla",
            ["int8_lane_blocks"]),
        # prefill: as decode for the head gate; a seq mesh is the ring's
        ("prefill", "head_gate"): (prefill, tp4, "pallas", ["head_gate"]),
        ("prefill", "head_dim"): (
            lambda: prefill(dd=48), auto, "xla", ["head_dim"]),
        ("prefill", "seq_mesh"): (prefill, seq, "ring", ["seq_mesh"]),
        ("prefill", "window_softcap"): (
            lambda: prefill(window=win), auto, "xla", ["window_softcap"]),
        # the chunk op alone has the int8 validation gate, and hands a
        # STATIC window to the ragged kernel
        ("chunk attention", "int8_not_validated"): (
            lambda: chunk(k=k8, v=v8), auto, "xla", ["int8_not_validated"]),
        ("chunk attention", "static_window"): (
            lambda: chunk(window=4), ("pallas_interpret", None, 1),
            ("ragged attention", "pallas_interpret"), []),
    }
    for op, call, small in (("decode", decode, None),
                            ("chunk attention", chunk, qc_small),
                            ("ragged mixed", mixed, qc_small),
                            ("ragged verify", verify, qc_small)):
        soft = (dict(window=win) if op in ("decode", "ragged verify")
                else dict(logit_cap=30.0))
        table[op, "window_softcap"] = (
            lambda call=call, soft=soft: call(**soft), auto, "xla",
            ["window_softcap"])
        table[op, "seq_mesh"] = (call, seq, "xla", ["seq_mesh"])
        if op == "decode":
            continue
        # chunk and ragged ops: every failed gate ends on XLA
        table[op, "head_gate"] = (call, tp4, "xla", ["head_gate"])
        table[op, "int8_lane_blocks"] = (
            lambda call=call: call(k=k82, v=v82), ("auto", None, 2), "xla",
            ["int8_lane_blocks"])
        if op == "chunk attention":
            table[op, "lane_gate"] = (
                lambda: chunk(small, k_small, k_small, num_kv_heads=2),
                auto, "xla", ["lane_gate"])
        else:
            table[op, "lane_gate"] = (
                lambda call=call, small=small: call(
                    q_small, small, k_small, k_small, num_kv_heads=2),
                auto, "xla", ["lane_gate"])
    for op, call in (("decode", decode), ("prefill", prefill),
                     ("chunk attention", chunk), ("ragged mixed", mixed),
                     ("ragged verify", verify)):
        # the backend by choice: nothing is a demotion, nothing counted
        table[op, "xla"] = (call, ("xla", None, 1), "xla", [])
        table[op, "kernel"] = (call, ("pallas_interpret", None, 1),
                               "pallas_interpret", [])
    return table


@pytest.mark.parametrize(
    "route", sorted(_route_calls()), ids=lambda r: "-".join(r).replace(" ", "_"))
def test_every_auto_to_xla_route_is_counted(monkeypatch, route):
    """Every (op, gate) pair ends on one implementation and counts one
    reason (dynamo_pallas_fallback_total): on a TPU `auto` resolves to the
    kernels, and each gate that then sends an op to XLA, or takes its mesh
    away, leaves exactly the counts the table says; a backend by choice
    counts nothing. The kernels' entry points are stubs here: what is
    pinned is the route, not the arithmetic."""
    import importlib

    import jax
    import jax.numpy as jnp

    from dynamo_tpu.ops import attention as att
    from dynamo_tpu.ops import pallas_attention as pa

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    called = []
    for op, (mod, name) in _KERNEL_OF.items():
        def stub(q, *a, _op=op, **k):
            called.append(_op)
            return jnp.zeros_like(q)
        monkeypatch.setattr(
            importlib.import_module("dynamo_tpu.ops." + mod), name, stub)
    if route == ("chunk attention", "int8_lane_blocks"):
        # past the validation gate, which is closed (the pool is int8)
        monkeypatch.setattr(pa, "CHUNK_KERNEL_INT8_HW_VALIDATED", True)
    thunk, scope, ends_on, counted = _route_calls()[route]
    if isinstance(ends_on, str):
        ends_on = (_COUNTED_AS.get(route[0], route[0]), ends_on)
    fell, impl = att.pallas_fallback_counts(), att.attention_impl_counts()
    with att.attention_context(*scope):
        out = thunk()
    assert np.all(np.isfinite(np.asarray(out)))

    def gained(now, before):
        return {k: v - before.get(k, 0) for k, v in now.items()
                if v != before.get(k, 0)}
    assert gained(att.pallas_fallback_counts(), fell) == {
        (ends_on[0], reason): 1 for reason in counted}
    assert gained(att.attention_impl_counts(), impl) == {ends_on: 1}
    kernel = ends_on[1] in ("pallas", "pallas_interpret")
    assert called == ([ends_on[0]] if kernel else [])


def test_xla_by_choice_is_not_a_demotion():
    """The same calls on a backend that resolved to XLA in the first place
    (CPU tests, --attention-backend xla) count nothing; and `auto` never
    reaches the interpreter — only its name does."""
    from dynamo_tpu.ops import attention as att

    before = att.pallas_fallback_counts()
    for thunk, (_, mesh, blocks), _, _ in _route_calls().values():
        if mesh is None:
            with att.attention_context("xla", None, blocks):
                thunk()
    assert att.pallas_fallback_counts() == before
    with att.attention_context("auto", None):
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
