"""The async pipeline against the synchronous order, for a model's own
engine tests. Mixed steps: a prompt of several chunks arrives while a
sequence decodes. Finishes: sequences leave a running batch one by one,
by `max_tokens` and by stop tokens. First tokens: prompts end beside a
sequence that keeps decoding, and their final chunk's program samples the
token and installs the row. Tokens and the kernels' counters must be the
same."""

import dataclasses

import jax.numpy as jnp
import numpy as np
import pytest

from dynamo_tpu.engine.config import EngineConfig
from dynamo_tpu.engine.engine import Engine
from dynamo_tpu.engine.request import GenRequest
from dynamo_tpu.observability.memory import MemoryAccountant


def prompt(seed: int, n: int):
    return [int(t) for t in np.random.default_rng(seed).integers(3, 500, n)]


def drain(eng) -> dict:
    out = {}
    while eng.has_work:
        for ev in eng.step():
            if ev.token_id >= 0:
                out.setdefault(ev.request_id, []).append(ev.token_id)
    return out


def slots_held(eng) -> int:
    return MemoryAccountant(eng).snapshot()["state_slots"]["held"]


def greedy_of(ref, hf_dict, eng, tokens, n_new: int):
    """The argmax of `ref` (a family's reference module, configured through
    its test file's `hf_dict`) at every generated position, teacher forced
    on `tokens` (prompt + what the engine gave)."""
    cfg = dataclasses.replace(eng.model_cfg, dtype="float32")
    logits = ref.forward(ref.Config.from_hf(hf_dict(cfg)),
                         ref.dequantize(eng.params), jnp.asarray(tokens))
    first = len(tokens) - n_new
    return [int(t) for t in np.argmax(logits[first - 1:-1], axis=-1)]


def engine_pair(cfg: dict):
    """Module-scoped fixtures of one configuration: the engine, and the
    oracle of the pipelined orders (async_scheduling off)."""
    @pytest.fixture(scope="module")
    def engine():
        return Engine(EngineConfig(**cfg))

    @pytest.fixture(scope="module")
    def sync_engine():
        return Engine(EngineConfig(**cfg, async_scheduling=False))

    return engine, sync_engine


def warm_then_serve(eng, short: int = 20, long: int = 60):
    """After warmup() no request compiles a program: not a short prompt
    whose decoders leave before it is done, nor a long one. Returns the
    long prompt and its six greedy tokens."""
    eng.warmup()
    before = eng.compiled_program_count()
    eng.add_request(GenRequest("a", prompt(5, short), max_tokens=2,
                               temperature=0.0, ignore_eos=True))
    eng.step()
    p = prompt(6, long)
    eng.add_request(GenRequest("b", p, max_tokens=6, temperature=0.0,
                               ignore_eos=True))
    toks = drain(eng)["b"]
    assert eng.compiled_program_count() == before
    return p, toks


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


def drive(eng, script, probe=None):
    """Step `eng` until idle; `script` maps a step number to a callable run
    before that step (arrivals: by the count of steps, so that both orders
    run the same programs over the same contexts). Per request: tokens,
    the finish reason and the chosen logprobs. `probe(eng)` runs after
    every step."""
    eng.reset_metrics()
    out = {}
    n = 0
    while eng.has_work or any(k >= n for k in script):
        if n in script:
            script[n](eng)
        for ev in eng.step():
            rec = out.setdefault(ev.request_id,
                                 {"tokens": [], "finish": None, "lp": []})
            if ev.token_id >= 0:
                rec["tokens"].append(ev.token_id)
                if ev.logprob is not None:
                    rec["lp"].append(ev.logprob)
            if ev.finished:
                rec["finish"] = ev.finish_reason
        if probe is not None:
            probe(eng)
        n += 1
    return out


def same_streams(out, ref):
    """Tokens and finish reasons equal, logprobs to 1e-4."""
    assert out.keys() == ref.keys()
    for rid in ref:
        assert out[rid]["tokens"] == ref[rid]["tokens"], rid
        assert out[rid]["finish"] == ref[rid]["finish"], rid
        assert out[rid]["lp"] == pytest.approx(ref[rid]["lp"], abs=1e-4), rid


def assert_first_token_rides_pipeline(sync_eng, eng, prompt):
    """Three prompts end beside an anchor that keeps decoding: a greedy
    one of three chunks with logprobs, a sampled one of one chunk under
    top-k, min-p and a logit bias, and a sampled one of two chunks under
    top-p with penalties (its later tokens read the count row that the
    program reset and gave the first token). `prompt(i, n)` = the i-th
    prompt, n tokens. `eng` (async scheduling) leaves every final chunk in
    flight (`first_tokens_behind` 3 of the 3 that arrive beside the
    anchor; the anchor itself finds an idle engine), opens no drained
    interval for them, gives the tokens and logprobs `sync_eng`
    (async_scheduling=False) gives, runs the same programs over the same
    contexts (the kernels' counters) and leaves every store as it was."""
    assert eng.cfg.async_scheduling and not sync_eng.cfg.async_scheduling
    c = eng.cfg.mixed_batch_tokens
    arrivals = {
        0: GenRequest("anchor", prompt(0, 3), max_tokens=60,
                      temperature=0.0, ignore_eos=True),
        3: GenRequest("greedy", prompt(1, 2 * c + 6), max_tokens=6,
                      temperature=0.0, ignore_eos=True, logprobs=2),
        9: GenRequest("picky", prompt(2, c - 1), max_tokens=7,
                      temperature=0.8, seed=7, top_k=8, min_p=0.05,
                      logit_bias={17: 1.5}, ignore_eos=True, logprobs=1),
        14: GenRequest("penalized", prompt(3, c + 3), max_tokens=9,
                       temperature=1.1, seed=9, top_p=0.9,
                       presence_penalty=0.5, frequency_penalty=0.3,
                       ignore_eos=True),
    }
    script = {at: (lambda e, r=r: e.add_request(dataclasses.replace(r)))
              for at, r in arrivals.items()}
    before = _stores(eng)
    seen = []

    def probe(e):
        pw = e._pending_win
        if pw is not None and pw.joiner is not None:
            # in flight past the end of its step(), the newcomer seated
            # without a token, and the prompt no longer inflight
            seq = e.seqs[pw.joiner.slot]
            assert e._inflight is None and not seq.output_tokens
            seen.append((pw.joiner.req.request_id, e.timeline.drained_count))

    want = drive(sync_eng, script)
    got = drive(eng, script, probe)
    same_streams(got, want)
    assert [len(got[rid]["lp"]) for rid in ("greedy", "picky")] == [6, 7]
    assert [rid for rid, _ in seen] == ["greedy", "picky", "penalized"]
    # the anchor's first dispatches found an idle engine; nothing since
    assert len({n for _, n in seen}) == 1
    assert eng.timeline.drained_count == seen[0][1]
    m, ref = eng.metrics, sync_eng.metrics
    assert (m.first_tokens_behind, m.num_admitted) == (3, 4)
    assert (ref.first_tokens_behind, ref.num_admitted) == (0, 4)
    assert m.mixed_count == ref.mixed_count == 6
    assert m.mixed_behind == m.mixed_count
    assert m.output_tokens == ref.output_tokens
    assert m.prompt_tokens == ref.prompt_tokens
    counters, ref_counters = m.kernel_counters(), ref.kernel_counters()
    for name in ("attn", "attn_kinds", "dsa", "ssm"):
        assert counters[name] == ref_counters[name], name
    assert _stores(eng) == before
    return got


def assert_windows_as_long_as_the_shortest_headroom(single, engines, prompt):
    """A greedy and a seeded sampled sequence end beside an anchor (greedy,
    logprobs) at every offset 1 .. k + 1 of a k-step window: the greedy one
    after n decode steps, the sampled one after 2k + 2 - n (an odd number
    of steps behind it). Every engine of `engines` (num_scheduler_steps =
    k, either order) gives the tokens, logprobs and finish reasons that
    `single` (num_scheduler_steps=1: a classic program a step) gives, runs
    the same decode steps over the same contexts (the kernels' counters),
    and the pipelined ones dispatched windows of every length the
    scheduler hands out: k, and 1 .. SHORT_WINDOW_STEPS under the
    shortest headroom (`metrics.windows`).
    `prompt(i)` = the i-th prompt, every round its own three."""
    assert single.cfg.num_scheduler_steps == 1
    k = engines[0].cfg.num_scheduler_steps
    lags = [set() for _ in engines]  # window lengths seen in flight
    for n in range(1, k + 2):
        reqs = [
            GenRequest("anchor", prompt(3 * n), max_tokens=3 * k + 3,
                       temperature=0.0, ignore_eos=True, logprobs=2),
            GenRequest("greedy", prompt(3 * n + 1), max_tokens=n + 1,
                       temperature=0.0, ignore_eos=True),
            GenRequest("sampled", prompt(3 * n + 2), max_tokens=2 * k + 3 - n,
                       temperature=0.9, seed=40 + n, top_k=8,
                       ignore_eos=True, logprobs=1)]
        script = {0: lambda e: [e.add_request(dataclasses.replace(r))
                                for r in reqs]}
        want = drive(single, script)
        assert [len(want[r.request_id]["tokens"]) for r in reqs] == [
            3 * k + 3, n + 1, 2 * k + 3 - n]
        for eng, seen in zip(engines, lags):
            assert eng.cfg.num_scheduler_steps == k > 1

            def probe(e, seen=seen):
                pw = e._pending_win
                if pw is not None and pw.chunk is None:
                    # a window ends ON a sequence's end or before it
                    assert 1 <= pw.lag <= min(
                        e._headroom(e.seqs[s]) for s in pw.slots)
                    assert pw.lag == k or pw.lag <= e.SHORT_WINDOW_STEPS
                    seen.add(pw.lag)

            same_streams(drive(eng, script, probe), want)
            m, ref = eng.metrics, single.metrics
            assert m.decode_steps == ref.decode_steps
            assert m.windows["steps"] == ref.windows["steps"]
            assert m.windows["programs"] < ref.windows["programs"]
            counters, ref_counters = m.kernel_counters(), ref.kernel_counters()
            for name in ("attn", "attn_kinds", "dsa", "ssm", "conv"):
                assert counters[name] == ref_counters[name], name
    for eng, seen in zip(engines, lags):
        if eng.cfg.async_scheduling:
            short = min(k, eng.SHORT_WINDOW_STEPS)
            assert seen == set(range(1, short + 1)) | {k}, sorted(seen)
