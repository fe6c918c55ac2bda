"""AOT warmup + per-role engine-config files."""

import json

import pytest

from dynamo_tpu.engine.config import EngineConfig
from dynamo_tpu.engine.engine import Engine
from dynamo_tpu.engine.request import GenRequest


def test_warmup_precompiles_everything():
    """After warmup(), serving real traffic compiles zero new programs."""
    eng = Engine(EngineConfig(
        model="tiny-debug", page_size=4, num_pages=64, max_num_seqs=2,
        max_seq_len=64, num_scheduler_steps=4))
    info = eng.warmup()
    assert info["programs"] > 0
    n = eng.compiled_program_count()
    # real traffic across both decode paths (single-step while pending,
    # fused window after) + a fresh prefill bucket size
    eng.add_request(GenRequest("w1", [1, 2, 3], max_tokens=12,
                               temperature=0.0, ignore_eos=True))
    eng.add_request(GenRequest("w2", [1, 2, 3, 4, 5, 6, 7], max_tokens=12,
                               temperature=0.7, seed=7, ignore_eos=True))
    # guided windows are reachable by any request (response_format) and
    # must be warm too — ignore_eos keeps the request alive past JSON
    # completion so the FUSED guided window actually dispatches, and the
    # logprobs variant selects the lp=True guided programs
    eng.add_request(GenRequest("w3", [1, 2, 3], max_tokens=12,
                               temperature=0.0, ignore_eos=True,
                               guided_json=True))
    eng.add_request(GenRequest("w4", [1, 2, 3], max_tokens=12,
                               temperature=0.0, ignore_eos=True,
                               guided_json=True, logprobs=1))
    while eng.has_work:
        eng.step()
    assert eng.compiled_program_count() == n, "traffic caused fresh compiles"


def test_warmup_preserves_live_sequences():
    eng = Engine(EngineConfig(
        model="tiny-debug", page_size=4, num_pages=64, max_num_seqs=2,
        max_seq_len=64))
    ref = eng.generate(GenRequest("a", [1, 2, 3], max_tokens=8,
                                  temperature=0.0, ignore_eos=True))
    eng.warmup()
    out = eng.generate(GenRequest("b", [1, 2, 3], max_tokens=8,
                                  temperature=0.0, ignore_eos=True))
    assert out == ref


def test_engine_config_file_overrides(tmp_path):
    f = tmp_path / "decode.yaml"
    f.write_text("num_scheduler_steps: 8\npage_size: 32\n")
    cfg = EngineConfig(model="x").apply_file(str(f))
    assert cfg.num_scheduler_steps == 8
    assert cfg.page_size == 32
    assert cfg.model == "x"  # untouched fields survive


def test_engine_config_file_rejects_unknown_keys(tmp_path):
    f = tmp_path / "bad.yaml"
    f.write_text("page_sizeee: 32\n")
    with pytest.raises(ValueError, match="page_sizeee"):
        EngineConfig().apply_file(str(f))


def test_engine_config_cli_integration(tmp_path):
    import argparse

    f = tmp_path / "role.json"
    f.write_text(json.dumps({"max_num_seqs": 3, "quantization": "int8"}))
    p = argparse.ArgumentParser()
    EngineConfig.add_cli_args(p)
    args = p.parse_args(["--model", "tiny-debug", "--engine-config", str(f)])
    cfg = EngineConfig.from_cli_args(args)
    assert cfg.max_num_seqs == 3
    assert cfg.quantization == "int8"
    assert cfg.warmup is True  # worker CLI default


def test_donation_is_resolved_by_parameter_name():
    """A TPU deletes a donated buffer where the CPU only warns, so no CPU
    test can see a wrong donate_argnums tuple; the engine therefore names
    what it donates and resolves positions from each signature."""
    from dynamo_tpu.engine.engine import _argnums

    def window_fn(params, tokens, positions, context_lens, active,
                  bias_ids, counts, k_pages, v_pages, *extra):
        pass

    assert _argnums(window_fn, "tokens", "positions", "context_lens",
                    "counts", "k_pages", "v_pages") == (1, 2, 3, 6, 7, 8)
    # the first *extra operand (guided grammar carry / lora slot) follows
    assert _argnums(window_fn, "extra") == (9,)
    with pytest.raises(ValueError):
        _argnums(window_fn, "slot_keys")  # not in this signature: loud
