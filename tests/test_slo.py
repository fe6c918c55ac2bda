"""SLO & profiling plane (ISSUE 6): deterministic burn-rate tracking,
OpenMetrics trace exemplars resolving to span trees, engine phase/MFU/MBU
exposition, and exposition validity (tests/metrics_lint.py)."""

import json
import re
import urllib.request

import pytest

from dynamo_tpu.engine.config import EngineConfig
from dynamo_tpu.engine.engine import Engine
from dynamo_tpu.observability import slo as obs_slo
from dynamo_tpu.operator import materialize as mat
from dynamo_tpu.serving.api import (
    ServingContext,
    make_server,
    serve_forever_in_thread,
)
from dynamo_tpu.serving.frontend import FrontendContext, make_frontend_server
from dynamo_tpu.serving.metrics import (
    Counter,
    FrontendMetrics,
    Gauge,
    Histogram,
    Registry,
)
from metrics_lint import assert_valid_scrape, lint_exposition

MODEL = "tiny-debug"


# ------------------------------------------------------- target loading --

def test_targets_from_env_scalars_and_json():
    env = {"DYNAMO_TPU_SLO_TTFT_MS": "500", "DYNAMO_TPU_SLO_GOAL": "0.95"}
    targets = obs_slo.targets_from_env(env)
    assert len(targets) == 1
    assert targets[0].ttft_ms == 500 and targets[0].goal == 0.95
    assert targets[0].model == "*" and targets[0].role == "*"

    env = {"DYNAMO_TPU_SLO_TARGETS": json.dumps([
        {"model": "m:adapter-a", "role": "decode", "itlMs": 40},
        {"ttft_ms": 300, "errorRate": 0.01},
    ])}
    targets = obs_slo.targets_from_env(env)
    assert len(targets) == 2
    assert targets[0].model == "m:adapter-a" and targets[0].itl_ms == 40
    assert targets[1].error_rate == 0.01

    # malformed JSON / unknown keys never raise out of env loading
    assert obs_slo.targets_from_env({"DYNAMO_TPU_SLO_TARGETS": "{"}) == []
    assert obs_slo.targets_from_env(
        {"DYNAMO_TPU_SLO_TARGETS": '[{"bogusKey": 1}]'}) == []
    with pytest.raises(ValueError):
        obs_slo.target_from_dict({"bogusKey": 1})


def test_operator_slo_env_materialization():
    # map form -> scalar envs, applied to frontend AND worker containers
    spec = {"sloTargets": {"ttftMs": 500, "goal": 0.99}}
    assert mat.slo_env(spec) == [("DYNAMO_TPU_SLO_GOAL", "0.99"),
                                 ("DYNAMO_TPU_SLO_TTFT_MS", "500")]
    # list form -> one JSON env the worker-side parser accepts verbatim
    spec = {"sloTargets": [{"model": "m", "itlMs": 40}]}
    (name, value), = mat.slo_env(spec)
    assert name == "DYNAMO_TPU_SLO_TARGETS"
    assert obs_slo.targets_from_env({name: value})[0].itl_ms == 40
    with pytest.raises(ValueError):
        mat.slo_env({"sloTargets": {"ttftMilliseconds": 1}})
    with pytest.raises(ValueError):
        mat.slo_env({"sloTargets": [{"nope": 1}]})

    cr = {"metadata": {"name": "g", "namespace": "d"},
          "spec": {"services": {
              "Frontend": {"componentType": "frontend",
                           "sloTargets": {"ttftMs": 250}},
              "Worker": {"componentType": "worker",
                         "sloTargets": [{"role": "decode", "itlMs": 50}]},
          }}}
    out = mat.materialize(cr)
    envs = {d["metadata"]["name"]:
            {e["name"]: e.get("value") for e in
             d["spec"]["template"]["spec"]["containers"][0]["env"]}
            for d in out["deployments"]}
    assert envs["g-frontend"]["DYNAMO_TPU_SLO_TTFT_MS"] == "250"
    assert "DYNAMO_TPU_SLO_TARGETS" in envs["g-worker"]


# ------------------------------------------------ deterministic burn rate --

def test_burn_rate_flips_and_recovers_under_fake_clock():
    """Acceptance: injected latency breaching the TTFT target flips
    dynamo_slo_burn_rate above 1.0 within one 5m window and recovers after
    the breach ends; /debug/slo history matches the injected request rate
    exactly."""
    m = FrontendMetrics()
    clock = [10_000.0]
    target = obs_slo.SLOTarget(ttft_ms=250, goal=0.99)
    eng = obs_slo.SLOEngine(m, role="frontend", targets=[target],
                            clock=lambda: clock[0], bucket_s=10)

    def drive(n_buckets, ttft_s, per_bucket=5):
        for _ in range(n_buckets):
            for _ in range(per_bucket):
                m.requests_total.inc(model=MODEL)
                m.ttft.observe(ttft_s, model=MODEL)
            eng.tick()
            clock[0] += 10

    # healthy traffic fills the whole 5m window: burn 0, attainment 1
    drive(30, 0.1)
    rows = {(r["objective"], r["window"]): r for r in eng.evaluate()}
    assert rows[("ttft", "5m")]["burn_rate"] == 0.0
    assert rows[("ttft", "5m")]["attainment"] == 1.0

    # breach: ONE bucket of slow traffic must already push the fast
    # window's burn above 1.0 (5/155 breaching ≈ 3.2% of a 1% budget)
    drive(1, 1.0)
    rows = {(r["objective"], r["window"]): r for r in eng.evaluate()}
    assert rows[("ttft", "5m")]["burn_rate"] > 1.0

    # sustained breach saturates the window
    drive(29, 1.0)
    rows = {(r["objective"], r["window"]): r for r in eng.evaluate()}
    assert rows[("ttft", "5m")]["attainment"] < 0.2
    assert rows[("ttft", "5m")]["burn_rate"] > 10.0

    # recovery: a full healthy window later the fast burn is back to 0,
    # while the 1h window still remembers the incident
    drive(31, 0.1)
    rows = {(r["objective"], r["window"]): r for r in eng.evaluate()}
    assert rows[("ttft", "5m")]["burn_rate"] == 0.0
    assert rows[("ttft", "1h")]["burn_rate"] > 1.0

    # gauges carry the same numbers
    eng.refresh_gauges()
    gauge_vals = {dict(lbl)["window"]: v
                  for lbl, v in eng.burn_gauge._values.items()}
    assert gauge_vals["5m"] == 0.0 and gauge_vals["1h"] > 1.0

    # request-rate history: EXACTLY the injected per-bucket rate
    hist = eng.history()
    complete = [h for h in hist if not h.get("partial")]
    assert complete, "history must retain closed buckets"
    assert all(h["requests"] == 5 for h in complete[-60:])


def test_error_rate_objective_burn():
    m = FrontendMetrics()
    clock = [0.0]
    eng = obs_slo.SLOEngine(
        m, role="frontend",
        targets=[obs_slo.SLOTarget(error_rate=0.01)],
        clock=lambda: clock[0], bucket_s=10)
    for _ in range(95):
        m.requests_total.inc(model=MODEL)
    for _ in range(5):
        m.requests_total.inc(model=MODEL)
        m.errors_total.inc(model=MODEL, code="503")
    clock[0] += 10
    rows = {r["window"]: r for r in eng.evaluate()
            if r["objective"] == "error_rate"}
    assert rows["5m"]["burn_rate"] == 5.0  # 5% observed / 1% allowed
    assert rows["5m"]["attainment"] == 0.95


def test_role_and_model_selectors():
    m = FrontendMetrics()
    clock = [0.0]
    targets = [obs_slo.SLOTarget(role="prefill", ttft_ms=250),
               obs_slo.SLOTarget(model="other-model", ttft_ms=250)]
    eng = obs_slo.SLOEngine(m, role="decode", targets=targets,
                            clock=lambda: clock[0])
    m.ttft.observe(5.0, model=MODEL)
    clock[0] += 10
    # neither target matches this role/model: no evaluations at all
    assert eng.evaluate() == []


# ------------------------------------------------- zero-default satellite --

def test_labeled_metrics_emit_no_phantom_unlabeled_series():
    r = Registry()
    Counter("plain_total", "h", r)
    Counter("labeled_total", "h", r, labelnames=("model",))
    Gauge("labeled_gauge", "h", r, labelnames=("state",))
    Histogram("labeled_seconds", "h", r, buckets=(1.0,),
              labelnames=("model",))
    text = r.expose()
    # label-less metric keeps its zero default
    assert "\nplain_total 0" in text
    # labeled metrics with no children: HELP/TYPE only, no sample lines
    assert "\nlabeled_total 0" not in text
    assert "\nlabeled_gauge 0" not in text
    assert "labeled_seconds_count 0" not in text
    assert "# TYPE labeled_total counter" in text
    # once a child exists, it is exposed normally
    Counter("labeled_total2", "h", r, labelnames=("model",)).inc(model="m")
    assert 'labeled_total2{model="m"} 1.0' in r.expose()
    assert_valid_scrape(r.expose())


# --------------------------------------------------------- e2e stack ----

@pytest.fixture(scope="module")
def stack():
    import os

    # SLO targets via the same envs the operator materializes; set BEFORE
    # the contexts are built so each process role loads them at init
    slo_env = {"DYNAMO_TPU_SLO_TTFT_MS": "500",
               "DYNAMO_TPU_SLO_ITL_MS": "100",
               "DYNAMO_TPU_SLO_ERROR_RATE": "0.01"}
    saved = {k: os.environ.get(k) for k in slo_env}
    os.environ.update(slo_env)
    try:
        engine = Engine(EngineConfig(model=MODEL, page_size=4, num_pages=128,
                                     max_num_seqs=4, max_seq_len=128))
        wctx = ServingContext(engine, MODEL)
        fctx = FrontendContext()
    finally:
        for k, v in saved.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v
    wsrv = make_server(wctx, "127.0.0.1", 0)
    serve_forever_in_thread(wsrv)
    worker_url = f"http://127.0.0.1:{wsrv.server_address[1]}"

    fsrv = make_frontend_server(fctx, "127.0.0.1", 0)
    serve_forever_in_thread(fsrv)
    frontend_url = f"http://127.0.0.1:{fsrv.server_address[1]}"
    urllib.request.urlopen(urllib.request.Request(
        frontend_url + "/internal/register",
        data=json.dumps({"url": worker_url, "model": MODEL, "mode": "agg",
                         "stats": {"max_num_seqs": 4, "free_pages": 100,
                                   "total_pages": 128}}).encode(),
        headers={"Content-Type": "application/json"}), timeout=10)
    yield {"frontend": frontend_url, "worker": worker_url,
           "fctx": fctx, "wctx": wctx}
    fsrv.shutdown()
    wsrv.shutdown()
    wctx.close()


def _chat(url, **kw):
    body = {"model": MODEL,
            "messages": [{"role": "user", "content": "slo check"}],
            "max_tokens": 4, "temperature": 0, "ignore_eos": True, **kw}
    req = urllib.request.Request(
        url + "/v1/chat/completions", data=json.dumps(body).encode(),
        headers={"Content-Type": "application/json"}, method="POST")
    return urllib.request.urlopen(req, timeout=120)


def _get(url, path, accept=None):
    req = urllib.request.Request(url + path)
    if accept:
        req.add_header("Accept", accept)
    return urllib.request.urlopen(req, timeout=30).read().decode()


def test_exemplar_resolves_to_span_tree(stack):
    """Acceptance: an exemplar emitted on a TTFT bucket resolves via
    /debug/spans?trace_id= to the span tree of that same request."""
    resp = _chat(stack["frontend"])
    resp.read()
    rid = resp.headers.get("X-Request-Id")
    assert rid and len(rid) == 32

    om = _get(stack["frontend"], "/metrics",
              accept="application/openmetrics-text")
    assert_valid_scrape(om, openmetrics=True)
    exemplars = re.findall(
        r'dynamo_frontend_time_to_first_token_seconds_bucket\{[^}]*\} '
        r'\d+ # \{trace_id="([0-9a-f]{32})"\}', om)
    assert rid in exemplars, "the request's trace id must ride a TTFT bucket"

    spans = json.loads(_get(stack["frontend"],
                            f"/debug/spans?trace_id={rid}"))
    names = {sp["name"] for rs in spans["resourceSpans"]
             for ss in rs["scopeSpans"] for sp in ss["spans"]}
    # the whole tree: frontend AND worker spans share the trace id (the
    # in-process collector is shared; in K8s each pod serves its slice)
    assert {"frontend.request", "router.pick", "worker.request"} <= names

    # satellite: ?name= prefix filtering scopes the payload
    worker_only = json.loads(_get(
        stack["frontend"], f"/debug/spans?trace_id={rid}&name=worker."))
    wnames = {sp["name"] for rs in worker_only["resourceSpans"]
              for ss in rs["scopeSpans"] for sp in ss["spans"]}
    assert wnames and all(n.startswith("worker.") for n in wnames)
    assert "droppedTotal" in worker_only

    # a PLAIN scrape carries no exemplar syntax (strict 0.0.4 parsers)
    plain = _get(stack["frontend"], "/metrics")
    assert " # {" not in plain
    assert_valid_scrape(plain)


def test_worker_exposes_engine_phase_and_utilization(stack):
    """Acceptance: worker /metrics exposes dynamo_engine_phase_seconds for
    all four phases plus MFU/MBU gauges (plus occupancy and jit series)."""
    _chat(stack["worker"]).read()
    text = _get(stack["worker"], "/metrics")
    assert_valid_scrape(text)
    for phase in ("prefill", "prefill_chunk", "decode_window",
                  "decode_step"):
        assert f'dynamo_engine_phase_seconds_bucket{{phase="{phase}"' in text
    # real observations landed in the phase histograms
    m = re.search(r'dynamo_engine_phase_seconds_count\{phase="prefill"\} '
                  r'(\d+)', text)
    assert m and int(m.group(1)) > 0
    assert "dynamo_engine_mfu" in text and "dynamo_engine_mbu" in text
    assert "dynamo_engine_batch_occupancy_bucket" in text
    m = re.search(r"dynamo_engine_batch_occupancy_count (\d+)", text)
    assert m and int(m.group(1)) > 0
    assert "dynamo_engine_jit_programs" in text
    assert "dynamo_spans_dropped_total" in text


def test_worker_exposes_the_devices_idle_time(stack):
    """The step timeline's device account on /metrics: idle time before
    each program as a histogram, the same time by the engine thread's
    segment as a counter; the host-gap histogram they replace is gone."""
    _chat(stack["worker"]).read()
    text = _get(stack["worker"], "/metrics")
    assert_valid_scrape(text)
    m = re.search(r"dynamo_engine_device_idle_seconds_count (\d+)", text)
    assert m and int(m.group(1)) > 0
    assert "dynamo_engine_device_idle_seconds_bucket{" in text
    by = dict(re.findall(
        r'dynamo_engine_device_idle_seconds_total\{segment="(\w+)"\} (\S+)',
        text))
    assert set(by) == {"admit", "page_alloc", "dispatch", "device_wait",
                       "detok", "bank", "untracked", "between_steps",
                       "no_work"}
    assert float(by["dispatch"]) > 0.0  # the first program's own launch
    assert "host_gap" not in text


def test_live_mfu_mbu_nonzero_with_forced_chip(stack, monkeypatch):
    """With a chip identity forced (CPU box), the scrape-window utilization
    math must produce a nonzero MFU/MBU after decode activity."""
    from dynamo_tpu.observability.engine_metrics import EngineMetricsBridge
    from dynamo_tpu.serving.metrics import Registry as _R

    monkeypatch.setenv("DYNAMO_TPU_CHIP", "v5e")
    bridge = EngineMetricsBridge(_R(), stack["wctx"].engine)
    assert bridge.chip is not None and bridge.chip.name == "v5e"
    _chat(stack["worker"]).read()
    bridge.refresh()
    mfu = bridge.mfu_gauge._values.get(())
    mbu = bridge.mbu_gauge._values.get(())
    assert mfu is not None and mfu > 0
    assert mbu is not None and mbu > 0
    # idle second refresh reports zero, never a stale value
    bridge.refresh()
    assert bridge.mfu_gauge._values.get(()) == 0.0


def test_debug_slo_endpoint(stack):
    # a STREAMING request: frontend ITL is observed per relayed block, so
    # the itl objective has a matching series at the frontend
    _chat(stack["frontend"], stream=True).read()
    payload = json.loads(_get(stack["frontend"], "/debug/slo"))
    assert payload["role"] == "frontend"
    assert payload["targets"] and payload["evaluations"]
    objectives = {r["objective"] for r in payload["evaluations"]}
    assert {"ttft", "itl", "error_rate"} <= objectives
    assert "history" not in payload
    with_hist = json.loads(_get(stack["frontend"], "/debug/slo?history=1"))
    assert isinstance(with_hist["history"], list) and with_hist["history"]
    assert sum(h["requests"] for h in with_hist["history"]) >= 1
    # burn gauges ride the frontend scrape after a refresh
    text = _get(stack["frontend"], "/metrics")
    assert "dynamo_slo_burn_rate" in text
    assert "dynamo_slo_attainment" in text
    # the worker serves /debug/slo too (role = its disagg mode)
    wp = json.loads(_get(stack["worker"], "/debug/slo"))
    assert wp["role"] == "agg"


def test_scrape_validation_openmetrics_worker(stack):
    om = _get(stack["worker"], "/metrics",
              accept="application/openmetrics-text")
    assert_valid_scrape(om, openmetrics=True)
    assert om.rstrip().endswith("# EOF")


def test_lint_catches_real_defects():
    """The validator itself must reject broken expositions."""
    bad_monotone = (
        'h_bucket{le="0.1"} 5\nh_bucket{le="1.0"} 3\n'
        'h_bucket{le="+Inf"} 3\nh_sum 1\nh_count 3\n')
    assert any("monotone" in e for e in lint_exposition(bad_monotone))
    bad_count = (
        'h_bucket{le="0.1"} 1\nh_bucket{le="+Inf"} 2\nh_sum 1\nh_count 3\n')
    assert any("_count" in e for e in lint_exposition(bad_count))
    assert any("unparseable" in e
               for e in lint_exposition('h{label="unclosed} 1\n'))
    raw_newline = 'g{model="a\nb"} 1\n'
    assert lint_exposition(raw_newline)  # raw newline breaks the line shape
    bad_exemplar = ('h_bucket{le="0.1"} 1 # {trace_id="x"} 5.0\n'
                    'h_bucket{le="+Inf"} 1\nh_sum 0.05\nh_count 1\n')
    assert any("above bucket" in e
               for e in lint_exposition(bad_exemplar, openmetrics=True))
