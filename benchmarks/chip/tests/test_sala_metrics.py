"""Tests of the twelve per-layer metrics of the cell `sala-longdoc-r80`
(PR 56) and of kernel_costs/sparse_block_attention.py. The twelve are NOT
listed in BENCHMARK.json (its 128 per-layer places are taken): they are
loaded here as run.py would load them once listed, through lib/spec's own
functions, and fed what a traced run of the cell on the chip recorded
(records/pr56-sala-longdoc-r80-metrics-fixture.json: the /worker/stats polled
at the window's two ends, with the capture's samples, and the traced slice's
operations with their own seconds). Run by hand like the others (no JAX, no
process):

    python -m pytest benchmarks/chip/tests/test_sala_metrics.py -q
"""

import json
import os
import sys
import types

import pytest

CHIP_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, CHIP_DIR)

from lib import spec  # noqa: E402

ROOFLINES = ("sparse_decode_attn_roofline", "sparse_mixed_attn_roofline",
             "ssm_decode_update_roofline", "ssm_chunk_scan_roofline")
LAYERS = {
    "sparse_decode_attn_roofline": "kernels",
    "sparse_mixed_attn_roofline": "kernels",
    "sparse_select_busy_pct": "kernels",
    "sparse_selected_share_pct": "kernels",
    "ssm_decode_update_roofline": "kernels",
    "ssm_chunk_scan_roofline": "kernels",
    "state_slots_held_pct": "KV manager",
    "kv_live_pages_peak_pct": "KV manager",
    "step_ms": "engine step", "host_share_pct": "engine step",
    "batch_occupancy_pct": "scheduler", "device_idle_pct": "device",
}
NAMES = tuple(n + ".longdoc" for n in LAYERS)
FIXTURE = os.path.join(CHIP_DIR, "records",
                       "pr56-sala-longdoc-r80-metrics-fixture.json")


def _read(name, ctx):
    mfile = spec._named_file("layer_metrics", name)
    return spec.load_reader(mfile["reader"]).read(ctx, dict(mfile["args"]))


@pytest.fixture(scope="module")
def recorded():
    with open(FIXTURE) as f:
        rec = json.load(f)
    trace = {"window_s": rec["trace"]["window_s"],
             "busy_s": rec["trace"]["busy_s"],
             "op_s": {name: s for s, name, _ in rec["trace"]["ops"]}}
    return types.SimpleNamespace(
        requests=[], window_s=48.0, trace=trace, fail_s=120.0,
        snapshots=[(0.0, rec["first"]), (48.0, rec["last"])])


@pytest.mark.parametrize("name", NAMES)
def test_each_file_is_what_a_listed_metric_is(name):
    with open(os.path.join(spec.REPO, "BENCHMARK.json")) as f:
        bench = json.load(f)
    mfile = spec._named_file("layer_metrics", name)
    assert set(mfile) == {"about", "layer", "unit", "better", "source",
                          "moves", "reader", "args"}
    assert mfile["layer"] == LAYERS[name.rsplit(".", 1)[0]]
    assert mfile["layer"] in {m["layer"] for m in bench["per_layer"]}
    assert mfile["moves"] == "tpot_mean_ms"
    assert mfile["reader"] == "or_zero"  # a program without the model: 0
    assert spec.NAME_RE.match(name) and spec.UNIT_RE.match(mfile["unit"])
    assert callable(spec.load_reader(mfile["args"]["reader"]).read)
    if name.rsplit(".", 1)[0] in ROOFLINES:
        assert mfile["unit"] == "%" and mfile["better"] == "higher"
        assert mfile["source"] == "device_trace"
    # ready and unlisted: the 128 places are taken
    assert name not in {m["name"] for m in bench["per_layer"]}
    assert len(bench["per_layer"]) == 128


def test_the_entries_of_the_cell_keep_the_drivers_form():
    """The driver refuses BENCHMARK.json where a `why` or a `source` is not 1
    to 200 printable characters (PR 56 was sent back once for a `why` of
    208), and lib/spec does not look."""
    with open(os.path.join(spec.REPO, "BENCHMARK.json")) as f:
        bench = json.load(f)
    config = next(c for c in bench["configs"]
                  if c["name"] == "minicpm-sala-w8a8-1chip")
    cell = next(w for w in bench["workloads"]
                if w["name"] == "sala-longdoc-r80")
    assert set(config) == {"name", "source", "file", "reduced", "why"}
    assert set(cell) == {"name", "config", "traffic", "chips", "why"}
    for text in (config["why"], config["source"], cell["why"]):
        assert 1 <= len(text) <= 200 and text.isprintable(), text
    for name in (config["name"], cell["name"], cell["traffic"]):
        assert spec.NAME_RE.match(name)
    assert bench["configs"][-1] is config and bench["workloads"][-1] is cell
    assert config["reduced"] == [] and cell["chips"] == 1


@pytest.mark.parametrize("name", NAMES)
def test_each_reads_the_recorded_run(recorded, name):
    value = _read(name, recorded)
    assert value is not None and value >= 0.0
    base = name.rsplit(".", 1)[0]
    if base in ROOFLINES:
        assert 0.0 < value <= 100.0, (name, value)
    if base == "sparse_selected_share_pct":
        assert 20.0 < value < 50.0  # 6,208 rows of 9 k-42 k contexts
    if base == "state_slots_held_pct":
        assert 0.0 < value <= 100.0


@pytest.mark.parametrize("name", NAMES)
def test_a_program_without_the_model_reads_zero_or_nothing(name):
    """The parent's /worker/stats has no metrics.sparse, no state slots and
    its trace no such operation: every file reads 0 and none raises."""
    empty = {"metrics": {}, "memory": {}, "timeline": {},
             "device_kind": "TPU v5 lite"}
    ctx = types.SimpleNamespace(
        requests=[], window_s=48.0, fail_s=120.0,
        trace={"window_s": 3.0, "busy_s": 3.0, "op_s": {"%fusion.1": 3.0}},
        snapshots=[(0.0, empty), (48.0, empty)])
    assert _read(name, ctx) == 0.0


def test_the_cost_counts_what_was_asked():
    from readers.kernel_roofline import _cost_module

    cost = _cost_module("sparse_block_attention")
    grew = {"metrics.sparse.rows_attended": 6208.0,
            "metrics.sparse.keys_scored": 1000.0,
            "metrics.sparse.chunk_queries": 256.0,
            "metrics.sparse.chunk_rows_attended": 256.0 * 6208,
            "metrics.sparse.chunk_keys_scored": 256.0 * 1000}
    args = dict(layers=8, heads=32, kv_heads=2, head_dim=128,
                chunk_tokens=256)
    dec = cost.from_counters(lambda p: grew.get(p, 0.0),
                             dict(args, which="decode"))
    assert dec["bytes"] == 8 * (6208 * 4 + 1000 * 4) * 2 * 128
    assert dec["ops"] == 8 * (6208 * 4 + 1000 * 2) * 32 * 128
    mix = cost.from_counters(lambda p: grew.get(p, 0.0),
                             dict(args, which="mixed"))
    assert mix["ops"] == 256 * dec["ops"]
    assert mix["bytes"] == dec["bytes"]  # one query's rows a chunk program
    assert dec["peak"] == "peak_bf16_flops_per_s"
