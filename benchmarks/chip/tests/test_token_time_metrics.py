"""Tests of the per-layer metrics that read the engine's token-time account
(PR 39: tpot_decode_ms, tpot_prompt_ms, tpot_drained_ms in every cell,
gap_max_engine_ms.chat in the chat cells) and of readers/stats_last.py. Run
by hand like the others (no JAX, no process):

    python -m pytest benchmarks/chip/tests -q
"""

import json
import os
import sys
import types

import pytest

CHIP_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, CHIP_DIR)

from lib import spec  # noqa: E402

THREE = ("tpot_decode_ms", "tpot_prompt_ms", "tpot_drained_ms")
FOURTH = "gap_max_engine_ms.chat"


def _snapshot(row_s, gaps, gap_max_s):
    return {"timeline": {"wall_s": 1.0, "steps": 3, "token_time": {
        "cause_s": {"decode": 0.0, "prompt": 0.0, "drained": 0.0},
        "row_s": dict(zip(("decode", "prompt", "drained"), row_s)),
        "gaps": gaps, "gap_max_s": gap_max_s, "worst": []}},
        "metrics": {}}


def _ctx(first, last):
    return types.SimpleNamespace(requests=[], window_s=48.0, trace=None,
                                 snapshots=[(0.0, first), (48.0, last)],
                                 fail_s=120.0)


def _cells():
    with open(os.path.join(spec.REPO, "BENCHMARK.json")) as f:
        return json.load(f)["workloads"]


@pytest.mark.parametrize("cell", [w["name"] for w in _cells()])
def test_every_cell_owes_the_three_and_the_chat_cells_the_fourth(cell):
    entry = next(w for w in _cells() if w["name"] == cell)
    owed = spec.load_cell(cell).owed(True)
    assert set(THREE) <= set(owed) and {owed[n] for n in THREE} == {"ms"}
    assert (FOURTH in owed) == (entry["traffic"] == "chat")
    # an untraced run owes none of them
    assert not (set(THREE) | {FOURTH}) & set(spec.load_cell(cell).owed(False))


def test_the_four_read_the_quotients_and_the_level():
    cell = spec.load_cell("qwen7b-chat-r80")
    by_name = {m.name: m for m in cell.per_layer}
    # lead-in left 2 s of waits over 100 tokens; the window adds 40 + 12 +
    # 1 s over 4,000 tokens
    ctx = _ctx(_snapshot((1.5, 0.4, 0.1), 100, 0.9),
               _snapshot((41.5, 12.4, 1.1), 4100, 1.234))
    got = {n: by_name[n].reader.read(ctx, by_name[n].args)
           for n in THREE + (FOURTH,)}
    assert got == pytest.approx({
        "tpot_decode_ms": 10.0, "tpot_prompt_ms": 3.0,
        "tpot_drained_ms": 0.25, FOURTH: 1234.0})
    # the three are one mean, split: what the engine's clock gives for
    # tpot_mean_ms over the window's emissions
    assert sum(got[n] for n in THREE) == pytest.approx(
        1e3 * (55.0 - 2.0) / 4000)


@pytest.mark.parametrize("case", ["no_account", "no_tokens",
                                  "one_snapshot", "no_snapshot"])
def test_the_four_read_zero_where_there_is_nothing_to_read(case):
    """The parent of PR 39 has no timeline.token_time: run.py cannot leave
    an owed metric out, so each reads 0 there and none raises."""
    cell = spec.load_cell("mixtral-chat-r80")
    old = {"timeline": {"wall_s": 1.0, "steps": 3}, "metrics": {}}
    still = _snapshot((1.0, 1.0, 1.0), 50, 0.0)
    ctx = {"no_account": _ctx(old, old), "no_tokens": _ctx(still, still),
           "one_snapshot": _ctx(old, old), "no_snapshot": _ctx(old, old)}[case]
    if case == "one_snapshot":
        ctx.snapshots = ctx.snapshots[:1]
    elif case == "no_snapshot":
        ctx.snapshots = []
    got = {m.name: m.reader.read(ctx, m.args) for m in cell.per_layer
           if m.name in THREE + (FOURTH,)}
    assert got == {n: 0.0 for n in THREE + (FOURTH,)}


def test_stats_last_reads_a_level_and_gives_nothing_without_one():
    reader = spec.load_reader("stats_last")
    args = {"path": "timeline.token_time.gap_max_s", "scale": 1000}
    ctx = _ctx(_snapshot((0, 0, 0), 0, 0.5), _snapshot((0, 0, 0), 0, 0.75))
    assert reader.read(ctx, args) == 750.0
    assert reader.read(ctx, {"path": "timeline.steps"}) == 3.0
    assert reader.read(ctx, {"path": "timeline.token_time.nope"}) is None
    assert reader.read(ctx, {"path": "timeline.wall_s.deeper"}) is None
    ctx.snapshots = []
    assert reader.read(ctx, args) is None


def test_queue_wait_is_listed_in_every_cell_under_a_metric_it_reports():
    with open(os.path.join(spec.REPO, "BENCHMARK.json")) as f:
        bench = json.load(f)
    by_mix = {"chat": "ttft_p95_ms", "agent": "tpot_mean_ms",
              "docqa": "tpot_mean_ms", "longmix": "tpot_mean_ms"}
    for w in bench["workloads"]:
        name = "queue_wait_mean_ms." + w["traffic"]
        (entry,) = [m for m in bench["per_layer"] if m["name"] == name]
        assert w["name"] in entry["workloads"]
        assert entry["moves"] == by_mix[w["traffic"]]
        (metric,) = [m for m in spec.load_cell(w["name"]).per_layer
                     if m.name == name]
        assert metric.args["num"] == ["metrics.first_token.queue_s"]
        assert metric.args["den"] == ["metrics.first_token.count"]
