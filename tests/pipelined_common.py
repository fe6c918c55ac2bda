"""Mixed steps behind the async pipeline against the synchronous order,
for a model's own engine tests: a prompt of several chunks arrives while a
sequence decodes; tokens and the kernels' counters must be the same."""

import dataclasses


def serve(eng, live, late, at=3):
    """`live` alone, `late` added before step `at` (by the count of steps
    and not of tokens read, so that both orders run the same programs over
    the same contexts): {id: tokens}."""
    eng.reset_metrics()
    eng.add_request(dataclasses.replace(live))
    got, n = {}, 0
    while eng.has_work:
        if n == at:
            eng.add_request(dataclasses.replace(late))
        for ev in eng.step():
            if ev.token_id >= 0:
                got.setdefault(ev.request_id, []).append(ev.token_id)
        n += 1
    return got


def assert_pipelined_matches_sync(sync_eng, eng, live, late):
    """`eng` (async scheduling) dispatched every mixed step behind a program
    in flight and gave what `sync_eng` (async_scheduling=False) gives."""
    assert eng.cfg.async_scheduling and not sync_eng.cfg.async_scheduling
    want, got = serve(sync_eng, live, late), serve(eng, live, late)
    assert got == want
    m, ref = eng.metrics, sync_eng.metrics
    assert m.mixed_count == ref.mixed_count >= 3
    assert (m.mixed_behind, ref.mixed_behind) == (m.mixed_count, 0)
    counters, ref_counters = m.kernel_counters(), ref.kernel_counters()
    for name in ("attn", "attn_kinds", "dsa", "ssm"):
        assert counters[name] == ref_counters[name], name
    return got
