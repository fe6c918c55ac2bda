"""Standalone exporter process: `python -m dynamo_tpu.exporter`.

Serves GET /metrics with the tpu_* hardware series — the role DCGM exporter
plays in the reference's GPU Operator install
(/root/reference/install-dynamo-1node.sh:266-286). Deployed by
deploy/tpu-metrics-exporter.yaml.
"""

from __future__ import annotations

import argparse
import logging
import os
import threading

from dynamo_tpu.exporter.tpu_exporter import TpuMetricsExporter
from dynamo_tpu.serving.http_base import JsonHTTPHandler, make_http_server


class _Handler(JsonHTTPHandler):
    exporter: TpuMetricsExporter  # bound by make_http_server

    def do_GET(self):
        if self.path == "/metrics":
            self._raw(200, self.exporter.registry.expose().encode(),
                      "text/plain; version=0.0.4")
        elif self.path in ("/health", "/live", "/ready"):
            self._json(200, {"status": "ok"})
        else:
            self._error(404, f"no route {self.path}")


def main(argv=None) -> None:
    logging.basicConfig(level=os.environ.get("LOG_LEVEL", "INFO"))
    p = argparse.ArgumentParser(prog="dynamo_tpu.exporter")
    p.add_argument("--host", default="0.0.0.0")
    p.add_argument("--port", type=int, default=int(os.environ.get("PORT", 9400)))
    p.add_argument("--interval", type=float,
                   default=float(os.environ.get("SCRAPE_INTERVAL", "10")))
    args = p.parse_args(argv)

    # A chip belongs to one process. On a node where an engine worker runs,
    # the worker holds the chips and exports these series in-process on its
    # own /metrics (serving/worker.py); this standalone process then cannot
    # initialise the TPU and must say so and exit — never come up on the
    # CPU and publish zero-valued tpu_* series as if they were a chip's.
    from dynamo_tpu.utils.platform import init_backend

    try:
        backend = init_backend()
    except SystemExit as e:
        raise SystemExit(
            f"{e}\ndynamo_tpu.exporter: if an engine worker runs on this "
            f"node it owns the chips and already serves the tpu_* series "
            f"on its own /metrics; the standalone exporter is only for "
            f"nodes whose chips no worker holds.") from e
    if backend != "tpu":
        raise SystemExit(
            "dynamo_tpu.exporter: refusing to export tpu_* hardware series "
            f"from a {backend} backend (JAX_PLATFORMS=cpu is set)")
    logging.info("tpu exporter on %s:%d", args.host, args.port)

    stop = threading.Event()
    exp = TpuMetricsExporter()
    t = threading.Thread(target=exp.run_forever, args=(args.interval, stop),
                         daemon=True)
    t.start()
    srv = make_http_server(_Handler, {"exporter": exp}, args.host, args.port)
    try:
        srv.serve_forever()
    finally:
        stop.set()


if __name__ == "__main__":
    main()
