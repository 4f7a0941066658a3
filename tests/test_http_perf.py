"""HTTP round-trip smoke benchmark (slow tier): tools/loadgen driving the
real worker-pool server + batcher + engine in-process on CPU.

Not a performance assertion (CPU numbers are meaningless for the TPU
north star) — a regression tripwire for the request path: zero errors
through keep-alive connection reuse, sane percentile accounting, and the
/stats surface operators depend on (occupancy, adaptive delay, reuse
counters) all live before a TPU run ever happens.
"""

import json
import threading
import urllib.request

import numpy as np
import pytest

from tensorflow_web_deploy_tpu.serving.batcher import Batcher
from tensorflow_web_deploy_tpu.serving.engine import InferenceEngine
from tensorflow_web_deploy_tpu.serving.http import (
    App, make_http_server, shutdown_gracefully,
)
from tensorflow_web_deploy_tpu.utils.config import ModelConfig, ServerConfig

pytestmark = pytest.mark.slow


def test_loadgen_roundtrip_zero_errors(request):
    from tools.loadgen import Recorder, closed_loop, percentile, synthetic_jpegs

    small_cls_pb = request.getfixturevalue("small_cls_pb")
    mc = ModelConfig(
        name="small_cls", pb_path=small_cls_pb, input_size=(96, 96),
        preprocess="inception", dtype="float32",
    )
    cfg = ServerConfig(
        model=mc, canvas_buckets=(256,), batch_buckets=(8,),
        max_delay_ms=5.0, request_timeout_s=60.0,
    )
    engine = InferenceEngine(cfg)
    engine.warmup()
    batcher = Batcher(engine, max_batch=8, max_delay_ms=5.0)
    batcher.start()
    app = App(engine, batcher, cfg)
    srv = make_http_server(app, "127.0.0.1", 0, pool_size=8)
    port = srv.server_address[1]
    threading.Thread(target=srv.serve_forever, daemon=True).start()
    url = f"http://127.0.0.1:{port}/predict"
    images = synthetic_jpegs(n=4, size=256)

    try:
        workers = 4
        rec = Recorder()
        closed_loop(url, images, workers, 4.0, 60.0, rec)

        assert rec.errors == 0, rec.sample_error
        assert len(rec.latencies_ms) > 0
        # Keep-alive: every worker holds ONE connection for the whole run.
        assert rec.connections == workers

        lat = sorted(rec.latencies_ms)
        p50, p99 = percentile(lat, 50), percentile(lat, 99)
        assert p50 is not None and p99 is not None
        assert 0 < p50 <= p99  # percentiles ordered and positive
        assert p99 <= max(lat)  # within observed range

        # /stats surfaces the operator view of the same run.
        with urllib.request.urlopen(f"http://127.0.0.1:{port}/stats", timeout=30) as r:
            snap = json.loads(r.read())
        assert snap["requests_total"] >= len(lat)
        assert snap["errors_total"] == 0
        assert snap["batch_occupancy"] is not None and 0 < snap["batch_occupancy"] <= 1
        assert 0.0 <= snap["batcher"]["adaptive_delay_ms"] <= snap["batcher"]["max_delay_ms"]
        http_snap = snap["http"]
        # Server-side reuse ratio agrees with the client: far more requests
        # than connections (the /stats GETs themselves add a connection).
        assert http_snap["requests_total"] > http_snap["connections_total"]
        staging = snap["staging"]
        assert staging["slabs_pooled"] >= 1
        # The pool's reuse counters: a run of one shape allocates a few
        # slabs and then takes them again; with every answer back, nothing
        # is out and the idle pool is at its floor.
        for k in ("slab_acquires_total", "slab_allocs_total", "slabs_out", "slabs_out_bytes"):
            assert isinstance(staging[k], int), k
        assert staging["slab_acquires_total"] > 2 * staging["slab_allocs_total"] > 0
        assert staging["slabs_out"] == 0 and staging["slabs_out_bytes"] == 0
        assert staging["slabs_pooled_bytes"] <= cfg.staging_pool_bytes
    finally:
        shutdown_gracefully(srv, batcher, grace_s=5.0)
