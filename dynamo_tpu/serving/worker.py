"""Engine-worker process entrypoint, specialised per backend profile.

The reference deploys three engine backends (vLLM / SGLang / TRT-LLM) that
share one serving contract but differ in scheduling philosophy. This repo
mirrors that as one TPU engine core specialised by three **backend
profiles** — each `python -m dynamo_tpu.<backend>` entrypoint selects a
distinct set of scheduling defaults (explicit CLI flags always win):

- ``jetstream``  — orchestrated serving: fixed fused decode windows driven
  synchronously (JetStream's orchestrator model); no chunked prefill —
  admission happens between windows.
- ``vllm_tpu``   — continuous batching: chunked prefill interleaved with
  decode, automatic prefix caching, async (overlapped) scheduling —
  vLLM's scheduler model.
- ``trtllm_tpu`` — the compiled-engine model: an explicit per-role
  ``--engine-config`` file is REQUIRED (TRT-LLM's engine_configs analogue,
  /root/reference/examples/dgdr/trtllm/disagg.yaml:39-40,64-65), AOT
  warmup always runs before /ready, and compiled programs persist in an
  engine cache directory (the TRT engine-build analogue).

CLI contract mirrors the reference's worker invocations
(`python3 -m dynamo.vllm --model ...`,
/root/reference/examples/deploy/vllm/agg.yaml:29-35; disagg role flags per
/root/reference/examples/deploy/vllm/disagg.yaml:37,57 and
/root/reference/examples/deploy/sglang/disagg.yaml:45-52), plus
`--frontend-url` for heartbeat registration with the frontend/router.
"""

from __future__ import annotations

import argparse
import json
import logging
import os
import signal
import socket
import threading
import time
import urllib.request

from dynamo_tpu.engine.config import EngineConfig
from dynamo_tpu.engine.engine import Engine
from dynamo_tpu.serving.api import ServingContext, make_server

log = logging.getLogger("dynamo_tpu.worker")

# Per-backend scheduling defaults (see module docstring). Applied as argparse
# defaults, so an explicit CLI flag always overrides its profile value.
# --speculative-mode is deliberately NOT a profile default: it is a
# workload bet (docs/perf.md "Speculative decoding v2" — pays off only on
# repetitive/agentic token streams), so the operator opts in per
# deployment; v2 composes with every profile here, including the
# chunked/mixed continuous-batching ones. Acceptance health lands on this
# worker's /metrics (dynamo_engine_spec_*) and /worker/stats `spec`.
BACKEND_PROFILES = {
    "jetstream": dict(
        num_scheduler_steps=8,
        async_scheduling=False,
        prefill_chunk_tokens=0,
        enable_prefix_caching=False,
    ),
    "vllm_tpu": dict(
        num_scheduler_steps=1,
        async_scheduling=True,
        prefill_chunk_tokens=256,
        enable_prefix_caching=True,
    ),
    "trtllm_tpu": dict(
        num_scheduler_steps=4,
        async_scheduling=True,
        prefill_chunk_tokens=256,
        enable_prefix_caching=True,
    ),
}


def _self_url(host: str, port: int) -> str:
    if host not in ("0.0.0.0", "::"):
        return f"http://{host}:{port}"
    # advertise the pod/host IP (downward-API env in K8s, hostname locally)
    adv = os.environ.get("POD_IP") or socket.gethostbyname(socket.gethostname())
    return f"http://{adv}:{port}"


def heartbeat_loop(ctx: ServingContext, frontend_url: str, self_url: str,
                   interval: float, stop: threading.Event):
    # HA frontend plane: --frontend-url may name N replicas
    # (comma-separated). The worker heartbeats to EVERY one so each
    # replica's registry is complete on its own — no replica depends on
    # another being alive to know this worker exists.
    payload_urls = [u.strip().rstrip("/") + "/internal/register"
                    for u in frontend_url.split(",") if u.strip()]
    first = True
    while True:
        if not first and stop.wait(interval):
            return
        first = False
        t_beat = time.monotonic()
        eng = ctx.engine
        body = json.dumps({
            "url": self_url,
            "model": ctx.served_model,
            "mode": eng.cfg.disaggregation_mode,
            "stats": {
                "active_seqs": eng.num_active,
                "pending": len(eng.pending),
                "free_pages": eng.allocator.free_pages,
                "total_pages": eng.cfg.num_pages,
                "max_num_seqs": eng.cfg.max_num_seqs,
                **({"kvbm_host_blocks": eng.cfg.kvbm_host_blocks,
                    "kvbm_peer_port": ctx.kvbm_source.port}
                   if ctx.kvbm_source is not None else {}),
                # multi-LoRA: device-RESIDENT adapters drive the router's
                # adapter-affinity pass; host-registered ones mark this
                # worker lazy-load capable for the fallback
                **({"adapters": sorted(eng.lora.resident()),
                    "adapters_available": eng.lora.names()}
                   if eng.lora is not None else {}),
                # preemptible batch pool membership (operator manifest
                # `preemptible: true`): frontends and the planner see
                # which capacity can vanish on a reclamation notice
                **({"preemptible": True} if ctx.preemptible else {}),
                # live elasticity: the active weight version, so the
                # rollout controller and the frontend fleet view can see
                # per-pod rollout progress without scraping each worker
                "weight_version": eng.weights.version,
                # per-tenant cost rollup rides the heartbeat so every
                # frontend replica can answer /debug/costs fleet-wide
                # without fanning out scrapes to each worker
                "costs": eng.cost.rollup(),
                # step-timeline bubble summary rides the same beat: the
                # frontend's /debug/timeline merges these fleet-wide
                "timeline": eng.timeline.summary(),
                # engine health (robustness/watchdog.py): the router
                # stops picking suspect/resurrecting/quarantined workers
                # and the planner excludes quarantined capacity
                "health": eng.watchdog.summary(),
            },
        }).encode()
        for payload_url in payload_urls:
            try:
                urllib.request.urlopen(
                    urllib.request.Request(
                        payload_url, data=body,
                        headers={"Content-Type": "application/json"},
                        method="POST",
                    ),
                    timeout=5,
                )
            except Exception as e:
                # one dead replica must not starve the others of beats
                log.warning("heartbeat to %s failed: %s", payload_url, e)
        took = time.monotonic() - t_beat
        if took > interval:
            # a frontend purges a worker whose beats stop for its TTL: a
            # beat slower than its interval is the first sign of that
            log.warning("heartbeat took %.2fs, longer than its %.1fs "
                        "interval (payload of %d bytes built and posted "
                        "to %d frontend(s))", took, interval, len(body),
                        len(payload_urls))


def build_parser(backend_name: str) -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(prog=f"dynamo_tpu.{backend_name}")
    EngineConfig.add_cli_args(p)
    p.set_defaults(**BACKEND_PROFILES.get(backend_name, {}))
    p.add_argument("--host", default="0.0.0.0")
    p.add_argument("--port", type=int, default=int(os.environ.get("PORT", 8000)))
    p.add_argument("--frontend-url", default=os.environ.get("FRONTEND_URL"))
    p.add_argument("--prefill-url", default=os.environ.get("PREFILL_URL"),
                   help="comma-separated prefill worker URLs (decode role)")
    p.add_argument("--heartbeat-interval", type=float, default=3.0)
    p.add_argument("--nats-url", default=os.environ.get("NATS_URL"),
                   help="NATS server URL: serve requests over the NATS "
                        "request plane in addition to HTTP")
    p.add_argument("--kvbm-peers", default=os.environ.get("KVBM_PEERS"),
                   help="comma-separated host:port peers whose KVBM host "
                        "tiers this worker may onboard prefix blocks from "
                        "(the cross-worker KV pull; ports from peers' "
                        "/worker/stats kvbm.peer_port)")
    p.add_argument("--coordinator", default=None,
                   help="jax.distributed coordinator host:port (multi-host "
                        "gang; the Grove-multinode analogue)")
    p.add_argument("--num-processes", type=int, default=None)
    p.add_argument("--process-id", type=int, default=None)
    return p


def main(argv=None, backend_name: str = "jetstream") -> None:
    logging.basicConfig(level=os.environ.get("LOG_LEVEL", "INFO"))
    p = build_parser(backend_name)
    args = p.parse_args(argv)

    if backend_name == "trtllm_tpu":
        # the compiled-engine contract: refuse to serve without an explicit
        # engine-build config (every profile persists compiled programs
        # through enable_compile_cache below, so a restart "loads the
        # engine" instead of rebuilding it)
        if not getattr(args, "engine_config", None):
            p.error("--engine-config FILE is required for the trtllm_tpu "
                    "backend (the TRT engine-build config analogue)")
    cfg = EngineConfig.from_cli_args(args)
    if backend_name == "trtllm_tpu" and not cfg.warmup:
        # the profile's defining contract (docs/backends.md): /ready never
        # precedes compile-completeness — not even --no-warmup or an
        # engine-config 'warmup: false' may break it
        log.warning("trtllm_tpu ignores warmup=false: the compiled-engine "
                    "profile always builds before serving")
        cfg.warmup = True
    from dynamo_tpu.parallel import distributed as dist

    dist_cfg = dist.resolve(args.coordinator, args.num_processes,
                            args.process_id)
    dist.initialize(dist_cfg)  # must precede the first backend touch
    from dynamo_tpu.utils.platform import enable_compile_cache, init_backend

    # this process owns the chip from here on: one in-process init, no
    # probe child, no CPU fallback (utils/platform.py)
    backend = init_backend()
    cache_dir = enable_compile_cache()
    log.info("starting %s worker: model=%s mode=%s tp=%d backend=%s "
             "process=%d/%d compile_cache=%s",
             backend_name, cfg.model, cfg.disaggregation_mode,
             cfg.tensor_parallel, backend, dist_cfg.process_id,
             dist_cfg.num_processes, cache_dir)
    engine = Engine(cfg)
    if cfg.warmup:
        # compile-complete before the socket opens: /ready can never observe
        # a worker that would stall first traffic on a multi-second XLA
        # compile (the reference's TRT engine-build happens pre-serve too)
        log.info("warming up: precompiling prefill buckets + decode windows")
        engine.warmup()
    if dist_cfg.enabled:
        plane = dist.ReplicationPlane(dist_cfg)
        if not dist_cfg.is_leader:
            # followers replay the leader's op stream; no HTTP surface
            dist.follower_loop(engine, plane)
            return
        engine = dist.ReplicatedEngine(engine, plane)
    ctx = ServingContext(
        engine, cfg.served_name,
        prefill_urls=(args.prefill_url.split(",") if args.prefill_url else None),
        frontend_url=args.frontend_url,
        kvbm_peers=(args.kvbm_peers.split(",") if args.kvbm_peers else None),
    )
    srv = make_server(ctx, args.host, args.port)

    if cfg.disaggregation_mode == "prefill":
        # colocated decode engines resolve this engine for the on-device
        # ici KV handoff (transfer.ici_registry); harmless cross-process
        from dynamo_tpu.transfer import ici_registry

        raw_engine = getattr(engine, "engine", engine)
        ici_registry.register(_self_url(args.host, srv.server_address[1]),
                              raw_engine)
        ici_registry.register(f"http://127.0.0.1:{srv.server_address[1]}",
                              raw_engine)

    # hardware series (tpu_tensorcore_utilization etc.) ride the same
    # /metrics endpoint — the in-process DCGM-analogue. In-process is the
    # primary path on TPU: the worker holds the chips (libtpu is
    # single-process), so only it can report real HBM/duty-cycle numbers.
    from dynamo_tpu.exporter.tpu_exporter import (
        attach_to_registry, engine_busy_sampler,
    )
    attach_to_registry(ctx.metrics.registry).set_sampler(
        engine_busy_sampler(engine)
    )

    nats_plane = None
    if args.nats_url:
        from dynamo_tpu.serving.nats_plane import WorkerNatsPlane

        try:
            nats_plane = WorkerNatsPlane(
                args.nats_url,
                f"http://127.0.0.1:{srv.server_address[1]}",
                cfg.served_name,
                advertised_url=_self_url(args.host, srv.server_address[1]),
            )
        except OSError as e:
            log.warning("NATS plane unavailable (%s); HTTP only", e)
        if nats_plane is not None and engine.prefix_cache is not None:
            # KV event plane: publish block stored/demoted/removed events
            # so the frontend's router can index this worker's real cache
            # contents (rides the request plane's NATS connection)
            from dynamo_tpu.kvbm.events import KVEventPublisher

            ctx.attach_kv_event_publisher(KVEventPublisher(
                nats_plane.nc,
                _self_url(args.host, srv.server_address[1]),
                cfg.served_name,
            ))
            log.info("kv event plane publishing on %s",
                     ctx.kv_event_publisher.subject)

    stop = threading.Event()
    hb_thread = None
    self_url = _self_url(args.host, args.port)
    if args.frontend_url:
        hb_thread = threading.Thread(
            target=heartbeat_loop,
            args=(ctx, args.frontend_url, self_url, args.heartbeat_interval, stop),
            daemon=True, name="heartbeat",
        )
        hb_thread.start()

    def shutdown(*_, deadline_s=None, wait=False):
        """Graceful drain (pod termination): stop admission (new requests
        shed 503 and the frontend fails them over), deregister from the
        frontend, give in-flight requests a grace window to finish, then
        ACTIVELY hand off journaled streams (the worker pushes its token
        journal back to the frontend, which splices a continuation on
        another replica) and demote prefix KV to the host tier for peer
        fetch. Bounded by DRAIN_TIMEOUT_S — align terminationGracePeriod
        with it. A second signal skips the drain.

        A spot reclamation notice (ServingContext.reclaim) runs this
        same, idempotent path with `deadline_s` as the HARD bound in
        place of the env budget, and `wait=True` so the notice thread
        can observe completion."""
        if stop.is_set():  # impatient second SIGTERM/SIGINT
            threading.Thread(target=srv.shutdown, daemon=True).start()
            return
        stop.set()

        def _drain():
            try:
                if deadline_s is not None:
                    # reclamation: leave margin inside the notice for the
                    # deregister round trips and the final KV demote
                    drain_s = max(1.0, deadline_s - 3.0)
                    grace_s = min(5.0, drain_s / 4.0)
                else:
                    try:
                        drain_s = float(
                            os.environ.get("DRAIN_TIMEOUT_S", "30"))
                    except ValueError:
                        log.warning("invalid DRAIN_TIMEOUT_S %r; using 30s",
                                    os.environ.get("DRAIN_TIMEOUT_S"))
                        drain_s = 30.0
                    try:
                        grace_s = float(os.environ.get(
                            "DRAIN_HANDOFF_GRACE_S", "5"))
                    except ValueError:
                        grace_s = 5.0
                # admission off FIRST: a request routed here between now
                # and the deregister sheds 503 and fails over cleanly
                ctx.begin_drain()
                if nats_plane is not None:
                    # stop consuming the NATS request plane NOW — new
                    # subjects must not refill the queue mid-drain
                    try:
                        nats_plane.close()
                    except Exception:
                        pass
                if args.frontend_url:
                    if hb_thread is not None:
                        # an IN-FLIGHT heartbeat register must land before
                        # the deregister, or it re-adds this worker
                        hb_thread.join(timeout=6.0)
                    # deregister from EVERY frontend replica the worker
                    # heartbeats to — a replica that misses the explicit
                    # deregister keeps routing here until the TTL expires
                    for fe in args.frontend_url.split(","):
                        fe = fe.strip()
                        if not fe:
                            continue
                        try:
                            urllib.request.urlopen(
                                urllib.request.Request(
                                    fe.rstrip("/") + "/internal/deregister",
                                    data=json.dumps(
                                        {"url": self_url}).encode(),
                                    headers={
                                        "Content-Type": "application/json"},
                                    method="POST",
                                ),
                                timeout=3,
                            ).close()
                        except Exception as e:
                            log.warning("deregister from %s failed (%s); "
                                        "that frontend will expire the "
                                        "heartbeat", fe, e)
                # grace: a request routed a moment before the deregister may
                # be accepted but not yet submitted — let it reach the
                # engine before the first empty check
                time.sleep(1.0)
                # drain state machine (api.ServingContext.drain): finish
                # naturally within the grace window, then hand off what
                # remains and demote prefix KV for peers
                if not ctx.drain(drain_s=drain_s,
                                 handoff_grace_s=min(grace_s, drain_s)):
                    log.warning(
                        "drain timeout with %d active / %d pending; "
                        "stopping anyway", engine.num_active,
                        len(engine.pending))
            finally:
                srv.shutdown()  # must run even if the drain itself blew up

        t = threading.Thread(target=_drain, daemon=True, name="drain")
        t.start()
        if wait:
            t.join()

    signal.signal(signal.SIGTERM, shutdown)
    signal.signal(signal.SIGINT, shutdown)
    # spot reclamation notices (POST /internal/reclaim, or a node
    # maintenance watcher POSTing to it) drive the same drain path under
    # the notice's hard deadline — deregister included
    ctx.reclaim_cb = lambda d: shutdown(deadline_s=d, wait=True)
    from dynamo_tpu.observability import tracing as obs_tracing

    log.info("worker listening on %s:%d (request tracing %s; spans at "
             "GET /debug/spans, kill switch DYNAMO_TPU_TRACE=0)",
             args.host, args.port,
             "on" if obs_tracing.tracing_enabled() else "off")
    if ctx.slo.targets:
        # SLO plane (docs/observability.md "SLOs and burn rates"): targets
        # come from DYNAMO_TPU_SLO_* — materialized by the operator from
        # the manifest's sloTargets key
        log.info("SLO targets active for role %s: %s (gauges on /metrics, "
                 "GET /debug/slo)", ctx.slo.role,
                 [t.label for t in ctx.slo.targets])
    try:
        srv.serve_forever()
    finally:
        if nats_plane is not None:
            nats_plane.close()
        ctx.close()  # stops the scheduler thread (and its idle_tick
        # broadcasts) BEFORE the shutdown broadcast below
        if dist_cfg.enabled and dist_cfg.is_leader:
            engine.shutdown()  # release followers from their collective


if __name__ == "__main__":
    main()
