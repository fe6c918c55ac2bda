"""The async pipeline against the synchronous order, for a model's own
engine tests. Mixed steps: a prompt of several chunks arrives while a
sequence decodes. Finishes: sequences leave a running batch one by one,
by `max_tokens` and by stop tokens. Tokens and the kernels' counters must
be the same."""

import dataclasses

from dynamo_tpu.engine.request import GenRequest


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


def _stores(eng):
    """What a finished run must leave as it found it: pages free or
    published, ring pages, decode slots; nothing held, nobody leaving."""
    cached = (eng.prefix_cache.stats()["entries"]
              if eng.prefix_cache is not None else 0)
    rings = (eng.win_rings.allocator.free_pages
             if eng.win_rings is not None else 0)
    return (eng.allocator.free_pages + cached, rings,
            sorted(eng._free_slots), list(eng._held), set(eng._leaving))


def run_all(eng, reqs, probe=None):
    """Every request from step 0 to idle: ({id: tokens}, {id: reason}).
    `probe(eng)` runs after every step."""
    eng.reset_metrics()
    for r in reqs:
        eng.add_request(dataclasses.replace(r))
    got, why = {r.request_id: [] for r in reqs}, {}
    while eng.has_work:
        for ev in eng.step():
            if ev.token_id >= 0:
                got[ev.request_id].append(ev.token_id)
            if ev.finished:
                why[ev.request_id] = ev.finish_reason
        if probe is not None:
            probe(eng)
    return got, why


def _first_seen_at(stream, lo):
    """(k, token): the first k >= lo whose token the stream has not shown
    before, so that stopping on it cuts the stream exactly there."""
    return next((k, t) for k, t in enumerate(stream)
                if k >= lo and stream.index(t) == k)


def assert_finish_rides_pipeline(sync_eng, eng, prompt,
                                 lengths=(41, 9, 17, 24)):
    """Seeded sampled sequences leave a running batch one by one while
    the longest keeps decoding: first each by its own `max_tokens`, then
    two of them on a stop token instead. `eng` (async scheduling) reads
    no program early for any of them (`finishes_behind`: every finish but
    that of the last sequence out, which leaves nobody to ride behind),
    gives the tokens `sync_eng` (async_scheduling=False) gives, runs the
    same programs over the same contexts where the ends are counted ahead
    (the kernels' counters; a stop token is found one program late, and
    that program has computed the leaver's row), holds nothing back past
    the program it was held for, and leaves every store as it was."""
    assert eng.cfg.async_scheduling and not sync_eng.cfg.async_scheduling
    n = len(lengths)
    assert n <= eng.cfg.max_num_seqs
    reqs = [GenRequest(f"f{i}", prompt(i), max_tokens=lengths[i],
                       temperature=0.9, seed=20 + i, ignore_eos=True)
            for i in range(n)]
    before = _stores(eng)
    held = []

    def probe(e):
        pw = e._pending_win
        held.append(len(e._held))
        # what is held waits for the program in flight and for no other
        assert all(pw is not None and t == pw.ticket for t, *_ in e._held)
        assert not e._leaving

    want, why_ref = run_all(sync_eng, reqs)
    got, why = run_all(eng, reqs, probe)
    assert got == want and why == why_ref
    assert [len(got[r.request_id]) for r in reqs] == list(lengths[:n])
    m, ref = eng.metrics, sync_eng.metrics
    assert m.num_finished == ref.num_finished == n
    assert (m.finishes_behind, ref.finishes_behind) == (n - 1, 0)
    assert m.decode_steps == ref.decode_steps
    counters, ref_counters = m.kernel_counters(), ref.kernel_counters()
    for name in ("attn", "attn_kinds", "dsa", "ssm"):
        assert counters[name] == ref_counters[name], name
    assert not any(held) and m.held_pages_peak == 0  # all counted ahead
    assert _stores(eng) == before

    # the same streams, two of them cut on a token of their own, late
    # enough that every prompt has been admitted by then
    stops = {}
    for r in reqs[-2:]:
        k, tok = _first_seen_at(want[r.request_id], 10)
        stops[r.request_id] = (k, tok)
    cut = [dataclasses.replace(r, stop_token_ids=[stops[r.request_id][1]])
           if r.request_id in stops else r for r in reqs]
    del held[:]
    want2, why_ref = run_all(sync_eng, cut)
    got2, why = run_all(eng, cut, probe)
    assert got2 == want2 and why == why_ref
    for rid, (k, tok) in stops.items():
        assert got2[rid] == want[rid][:k + 1] and why[rid] == "stop"
    assert m is not eng.metrics  # reset: this run's own counts
    m = eng.metrics
    assert m.num_finished == n and m.finishes_behind == n - 1
    # found at the read: the program behind it stayed in flight, and the
    # leaver's pages and slot waited for it
    assert any(held) and m.held_pages_peak > 0
    assert sync_eng.metrics.held_pages_peak == 0
    assert _stores(eng) == before
    return got, got2
