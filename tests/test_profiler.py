"""SLA profiler (aiconfigurator analogue) tests.

Contract under test mirrors /root/reference/examples/dgdr/trtllm/dgdr.yaml:22-31:
an SLA block (isl/osl/ttft/itl) + a system profile produce a concrete engine
config (parallelism, batch, replica split) written back into the DGD.
"""

import json

from dynamo_tpu.models.config import ModelConfig
from dynamo_tpu.profiler import best_config, get_system, sweep
from dynamo_tpu.profiler.configurator import (
    ANNOTATION,
    apply_sla_overrides,
    disagg_split,
)
from dynamo_tpu.profiler.roofline import estimate, param_count


def test_param_count_llama8b_close_to_8b():
    cfg = ModelConfig.from_model_name("meta-llama-3-8b-instruct")
    p = param_count(cfg)
    assert 7.5e9 < p < 8.5e9


def test_param_count_mixtral_total_vs_active():
    from dynamo_tpu.profiler.roofline import active_param_count

    cfg = ModelConfig.from_model_name("mixtral-8x7b-instruct-v0.1")
    total, active = param_count(cfg), active_param_count(cfg)
    assert 44e9 < total < 50e9        # ~46.7B
    assert 11e9 < active < 14.5e9     # ~12.9B
    assert active < total


def test_param_count_qwen3_moe():
    from dynamo_tpu.profiler.roofline import active_param_count

    cfg = ModelConfig.from_model_name("qwen3-30b-a3b")
    total, active = param_count(cfg), active_param_count(cfg)
    assert 29e9 < total < 32e9        # ~30.5B
    assert 2.7e9 < active < 3.6e9     # ~3.3B active


def test_sweep_8b_on_v5e8_meets_reference_sla():
    cfg = ModelConfig.from_model_name("meta-llama-3-8b-instruct")
    best = best_config(cfg, get_system("v5e-8"), 4000, 500, ttft_ms=600, itl_ms=25)
    assert best is not None
    assert best.meets(600, 25)
    assert best.tp * best.replicas <= 8
    assert best.tok_s_per_chip > 100


def test_70b_does_not_fit_single_v5e():
    cfg = ModelConfig.from_model_name("meta-llama-3-70b-instruct")
    assert sweep(cfg, get_system("v5e-1"), 4000, 500) == []
    assert best_config(cfg, get_system("v5e-1"), 4000, 500) is None


def test_70b_fits_v5p64():
    cfg = ModelConfig.from_model_name("meta-llama-3-70b-instruct")
    best = best_config(cfg, get_system("v5p-64"), 4000, 500, 600, 25)
    assert best is not None and best.feasible


def test_unmet_sla_falls_back_to_best_feasible():
    cfg = ModelConfig.from_model_name("meta-llama-3-8b-instruct")
    # 0.01ms ITL is unmeetable; posture is warn-and-continue, not refuse
    best = best_config(cfg, get_system("v5e-8"), 4000, 500, ttft_ms=600, itl_ms=0.01)
    assert best is not None
    assert not best.meets(600, 0.01)


def test_estimate_monotonic_in_model_size():
    small = ModelConfig.from_model_name("llama-3.2-1b-instruct")
    big = ModelConfig.from_model_name("meta-llama-3-8b-instruct")
    sys8 = get_system("v5e-8")
    e_small = estimate(small, sys8, 8, 32, 4000, 500)
    e_big = estimate(big, sys8, 8, 32, 4000, 500)
    assert e_small.tok_s_per_chip > e_big.tok_s_per_chip
    assert e_small.ttft_s < e_big.ttft_s


def test_disagg_split_sums_to_replicas():
    cfg = ModelConfig.from_model_name("qwen3-0.6b")
    est = best_config(cfg, get_system("v5e-16"), 4000, 500)
    split = disagg_split(est, 4000, 500)
    assert split["prefill"] >= 1 and split["decode"] >= 1
    assert split["prefill"] + split["decode"] == est.replicas


def test_disagg_split_none_for_single_replica_group():
    import dataclasses

    cfg = ModelConfig.from_model_name("meta-llama-3-8b-instruct")
    est = best_config(cfg, get_system("v5e-8"), 4000, 500)
    est1 = dataclasses.replace(est, replicas=1)
    assert disagg_split(est1, 4000, 500) is None


def test_apply_sla_overrides_no_model_flag_skips():
    dgd = _disagg_dgd("x")
    for svc in dgd["spec"]["services"].values():
        pod = svc.get("extraPodSpec")
        if pod:
            pod["mainContainer"]["args"] = ["--port", "8000"]
    before = json.dumps(dgd["spec"])
    out = apply_sla_overrides(dgd, {"isl": 100, "osl": 10}, system="v5e-8")
    decision = json.loads(out["metadata"]["annotations"][ANNOTATION])
    assert decision["result"] == "skipped"
    assert json.dumps(out["spec"]) == before


def test_apply_sla_overrides_unknown_model_skips():
    dgd = _disagg_dgd("no-such-model-xyz")
    before = json.dumps(dgd["spec"])
    out = apply_sla_overrides(dgd, {"isl": 100, "osl": 10}, system="v5e-8")
    decision = json.loads(out["metadata"]["annotations"][ANNOTATION])
    assert decision["result"] == "skipped"
    assert json.dumps(out["spec"]) == before


def test_apply_sla_overrides_disagg_needs_two_replica_groups():
    # 70B on v5e-8: even int8 weights at tp=8 leave only ONE replica group
    # -> disagg infeasible, template left unchanged rather than doubling the
    # chip demand
    dgd = _disagg_dgd("meta-llama-3-70b-instruct")
    before = json.dumps(dgd["spec"])
    out = apply_sla_overrides(dgd, {"isl": 4000, "osl": 500}, system="v5e-8")
    decision = json.loads(out["metadata"]["annotations"][ANNOTATION])
    assert decision["result"] == "disagg_infeasible"
    assert json.dumps(out["spec"]) == before


def test_quant_tier_unlocks_disagg_on_small_chips():
    # 70B bf16 on v5e-16 fits only at tp=16 (one group); the w8a8 tier
    # halves the weight footprint, so tp=8 x 2 replica groups fits and the
    # profiler recommends the quantization levers it needed
    dgd = _disagg_dgd("meta-llama-3-70b-instruct")
    out = apply_sla_overrides(dgd, {"isl": 4000, "osl": 500}, system="v5e-16")
    decision = json.loads(out["metadata"]["annotations"][ANNOTATION])
    assert decision["quantization"] == "w8a8"
    assert decision["replicas"] >= 2
    args = out["spec"]["services"]["DecodeWorker"]["extraPodSpec"][
        "mainContainer"]["args"]
    assert "--quantization" in args
    assert args[args.index("--quantization") + 1] == "w8a8"


def test_quant_tier_prefers_unquantized_when_sufficient():
    # 1B on v5e-8 meets a lax SLA without quantization: no --quantization /
    # --kv-cache-dtype flags are injected (quantization costs accuracy and
    # must only be recommended when needed)
    dgd = _disagg_dgd("llama-3.2-1b-instruct")
    out = apply_sla_overrides(dgd, {"isl": 1000, "osl": 100}, system="v5e-8")
    decision = json.loads(out["metadata"]["annotations"][ANNOTATION])
    assert decision["quantization"] == "none"
    assert decision["kv_cache_dtype"] == "auto"
    args = out["spec"]["services"]["DecodeWorker"]["extraPodSpec"][
        "mainContainer"]["args"]
    assert "--quantization" not in args
    assert "--kv-cache-dtype" not in args


def test_apply_sla_overrides_multi_host_topology():
    # 70B on v5p-64: tp=8 spans 2 v5p hosts (4 chips/host) -> the profiler
    # writes hostsPerReplica + per-HOST tpu limits so the materialized gang
    # StatefulSet is actually schedulable
    dgd = _disagg_dgd("meta-llama-3-70b-instruct")
    out = apply_sla_overrides(
        dgd, {"isl": 4000, "osl": 500, "ttft": 600, "itl": 25},
        system="v5p-64")
    decision = json.loads(out["metadata"]["annotations"][ANNOTATION])
    assert decision["hosts_per_replica"] == 2
    svc = out["spec"]["services"]["DecodeWorker"]
    assert svc["hostsPerReplica"] == 2
    assert svc["resources"]["limits"]["tpu"] == "4"


def test_apply_sla_overrides_removes_stale_quant_flags():
    # a re-applied DGD whose earlier decision quantized must lose the
    # levers when the new winner is the unquantized tier
    dgd = _disagg_dgd("llama-3.2-1b-instruct")
    for name in ("PrefillWorker", "DecodeWorker"):
        dgd["spec"]["services"][name]["extraPodSpec"]["mainContainer"][
            "args"] += ["--quantization", "w8a8", "--kv-cache-dtype", "int8"]
    out = apply_sla_overrides(dgd, {"isl": 1000, "osl": 100}, system="v5e-8")
    decision = json.loads(out["metadata"]["annotations"][ANNOTATION])
    assert decision["quantization"] == "none"
    args = out["spec"]["services"]["DecodeWorker"]["extraPodSpec"][
        "mainContainer"]["args"]
    assert "--quantization" not in args
    assert "--kv-cache-dtype" not in args


def test_int8_kv_roofline_models_lane_blocking():
    from dynamo_tpu.profiler.roofline import kv_bytes_per_token

    cfg = ModelConfig.from_model_name("meta-llama-3-70b-instruct")
    # 8 KV heads x dim 128: tp=8 pads every 1-head block to 256 lanes —
    # int8 KV saves NOTHING there, and the model must say so
    assert kv_bytes_per_token(cfg, "int8", tp=8) == \
        kv_bytes_per_token(cfg, "auto")
    # at tp=1 the packed layout really does halve (modulo scale lanes)
    assert kv_bytes_per_token(cfg, "int8", tp=1) < \
        0.6 * kv_bytes_per_token(cfg, "auto")


def test_get_system_parses_arbitrary_shape():
    s = get_system("v6e-512")
    assert s.num_chips == 512 and s.chip.name == "v6e"


def _disagg_dgd(model: str):
    worker = lambda role: {  # noqa: E731
        "componentType": "worker",
        "subComponentType": role,
        "replicas": 1,
        "extraPodSpec": {"mainContainer": {
            "args": ["--model", model, "--tp", "1"],
        }},
    }
    return {
        "apiVersion": "tpu.dynamo.ai/v1alpha1",
        "kind": "DynamoGraphDeployment",
        "metadata": {"name": "t"},
        "spec": {"services": {
            "Frontend": {"componentType": "frontend", "replicas": 1},
            "PrefillWorker": worker("prefill"),
            "DecodeWorker": worker("decode"),
        }},
    }


def test_apply_sla_overrides_rewrites_workers():
    dgd = _disagg_dgd("meta-llama-3-8b-instruct")
    out = apply_sla_overrides(
        dgd, {"isl": 4000, "osl": 500, "ttft": 600, "itl": 25}, system="v5e-16"
    )
    decision = json.loads(out["metadata"]["annotations"][ANNOTATION])
    assert decision["meets_sla"] is True
    svcs = out["spec"]["services"]
    for name in ("PrefillWorker", "DecodeWorker"):
        args = svcs[name]["extraPodSpec"]["mainContainer"]["args"]
        tp = int(args[args.index("--tp") + 1])
        assert tp == decision["tp"]
        assert args.count("--tp") == 1, "must replace, not duplicate"
        assert svcs[name]["resources"]["limits"]["tpu"] == str(tp)
    # split across the two pools covers the slice's replica groups
    total = svcs["PrefillWorker"]["replicas"] + svcs["DecodeWorker"]["replicas"]
    assert total == max(decision["replicas"], 2)
    # frontend untouched
    assert "resources" not in svcs["Frontend"]


def test_apply_sla_overrides_infeasible_annotates_only():
    dgd = _disagg_dgd("meta-llama-3-70b-instruct")
    before = json.dumps(dgd["spec"])
    out = apply_sla_overrides(dgd, {"isl": 4000, "osl": 500}, system="v5e-1")
    decision = json.loads(out["metadata"]["annotations"][ANNOTATION])
    assert decision["result"] == "infeasible"
    assert json.dumps(out["spec"]) == before


def test_profiler_cli_json(capsys):
    from dynamo_tpu.profiler.__main__ import main

    main(["--model", "meta-llama-3-8b-instruct", "--system", "v5e-16",
          "--isl", "4000", "--osl", "500", "--ttft", "600", "--itl", "25",
          "--json"])
    out = json.loads(capsys.readouterr().out)
    assert out["best"]["meets_sla"] is True
    split = out["disagg_split"]
    assert split is None or split["prefill"] >= 1
