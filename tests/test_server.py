"""Integration: full server over a real socket, 8-device CPU mesh.

SURVEY.md §4 integration row: start the server on localhost, POST a real
JPEG, assert the JSON response — the reference's entire operator workflow.
"""

import io
import json
import threading
import urllib.error
import urllib.request

import numpy as np
import pytest

from tensorflow_web_deploy_tpu.serving.batcher import Batcher
from tensorflow_web_deploy_tpu.serving.engine import InferenceEngine
from tensorflow_web_deploy_tpu.serving.http import App, make_http_server
from tensorflow_web_deploy_tpu.utils.config import ModelConfig, ServerConfig


def _jpeg(rng, h=120, w=90):
    from PIL import Image

    buf = io.BytesIO()
    Image.fromarray((rng.rand(h, w, 3) * 255).astype(np.uint8)).save(buf, "JPEG")
    return buf.getvalue()


@pytest.fixture(scope="module")
def cls_server(request):
    small_cls_pb = request.getfixturevalue("small_cls_pb")
    mc = ModelConfig(
        name="small_cls", pb_path=small_cls_pb, input_size=(96, 96),
        preprocess="inception", dtype="float32",
    )
    cfg = ServerConfig(
        model=mc, canvas_buckets=(128,), batch_buckets=(8,),
        max_delay_ms=5.0, request_timeout_s=60.0,
        # Above this module's total request count: the span-tiling test
        # looks its request up on the slowest board, and a fast request
        # (decode-into-slab made late requests quick) must not get bumped
        # by the module's earlier cold-start traffic.
        flight_recorder_n=512,
    )
    engine = InferenceEngine(cfg)
    engine.warmup()
    batcher = Batcher(engine, max_batch=8, max_delay_ms=5.0)
    batcher.start()
    app = App(engine, batcher, cfg)
    srv = make_http_server(app, "127.0.0.1", 0)
    port = srv.server_address[1]
    t = threading.Thread(target=srv.serve_forever, daemon=True)
    t.start()
    yield f"http://127.0.0.1:{port}", engine
    srv.shutdown()
    batcher.stop()


def _post(url, data, ctype="image/jpeg"):
    req = urllib.request.Request(url, data=data, method="POST")
    req.add_header("Content-Type", ctype)
    with urllib.request.urlopen(req, timeout=120) as r:
        return r.status, json.loads(r.read())


def _get(url):
    with urllib.request.urlopen(url, timeout=30) as r:
        return r.status, r.read()


def test_predict_raw_body(cls_server, rng):
    base, _ = cls_server
    status, resp = _post(f"{base}/predict?topk=3", _jpeg(rng))
    assert status == 200
    assert len(resp["predictions"]) == 3
    p = resp["predictions"][0]
    assert set(p) == {"label", "index", "score"}
    assert resp["model"] == "small_cls"
    # softmax output: scores in (0,1), descending
    scores = [q["score"] for q in resp["predictions"]]
    assert all(0 <= s <= 1 for s in scores) and scores == sorted(scores, reverse=True)


def test_predict_multipart(cls_server, rng):
    base, _ = cls_server
    boundary = "testboundary42"
    jpeg = _jpeg(rng)
    body = (
        f"--{boundary}\r\n"
        'Content-Disposition: form-data; name="image"; filename="t.jpg"\r\n'
        "Content-Type: image/jpeg\r\n\r\n"
    ).encode() + jpeg + f"\r\n--{boundary}--\r\n".encode()
    status, resp = _post(
        f"{base}/predict", body, ctype=f"multipart/form-data; boundary={boundary}"
    )
    assert status == 200
    assert len(resp["predictions"]) == 5


def test_predict_concurrent_requests_batched(cls_server, rng):
    import concurrent.futures as cf

    base, _ = cls_server
    jpeg = _jpeg(rng)
    with cf.ThreadPoolExecutor(8) as ex:
        results = list(ex.map(lambda _: _post(f"{base}/predict", jpeg), range(16)))
    assert all(s == 200 for s, _ in results)
    # identical inputs → identical outputs regardless of batch composition
    first = results[0][1]["predictions"]
    for _, resp in results[1:]:
        assert resp["predictions"] == first


def test_empty_body_400(cls_server):
    base, _ = cls_server
    try:
        _post(f"{base}/predict", b"")
        assert False, "expected 400"
    except urllib.error.HTTPError as e:
        assert e.code == 400


def test_garbage_body_400(cls_server):
    base, _ = cls_server
    try:
        _post(f"{base}/predict", b"not an image at all")
        assert False, "expected 400"
    except urllib.error.HTTPError as e:
        assert e.code == 400
        assert "could not decode" in json.loads(e.read())["error"]


def test_healthz(cls_server):
    base, _ = cls_server
    status, body = _get(f"{base}/healthz")
    data = json.loads(body)
    assert status == 200 and data["ok"] is True
    assert data["devices"] == 8  # fake 8-device CPU mesh


def test_stats(cls_server, rng):
    base, _ = cls_server
    _post(f"{base}/predict", _jpeg(rng))  # self-sufficient: don't rely on
    status, body = _get(f"{base}/stats")  # earlier tests' traffic
    snap = json.loads(body)
    assert status == 200
    assert snap["requests_total"] > 0
    assert "latency_ms" in snap and "batch_size_histogram" in snap
    # live config echo: the knobs that explain the latency numbers
    cfg = snap["config"]
    assert cfg["wire_format"] in ("rgb", "yuv420") and isinstance(cfg["packed_io"], bool)
    assert cfg["batch_buckets"] == [8] and cfg["devices"] == 8
    assert cfg["http_protocol"] == "HTTP/1.1 keep-alive"
    # request-path observability: occupancy, live adaptive window, reuse
    assert "batch_occupancy" in snap
    assert 0.0 <= snap["batcher"]["adaptive_delay_ms"] <= snap["batcher"]["max_delay_ms"]
    assert snap["http"]["connections_total"] >= 1
    assert snap["http"]["requests_total"] >= 1
    assert snap["staging"]["slab_allocs_total"] >= 1


def test_staging_reuse_counters_in_stats_and_metrics(cls_server, rng):
    """/stats -> staging and /metrics carry the pool's reuse counters:
    acquisitions beside allocations (reuse share = 1 - allocs / acquires),
    and the slabs and bytes that are out. A second request of a shape the
    pool already holds raises the acquisitions and not the allocations."""
    from tensorflow_web_deploy_tpu.utils.metrics import parse_prometheus_text

    base, _ = cls_server
    fields = ("slab_acquires_total", "slab_allocs_total", "slabs_out", "slabs_out_bytes")

    def staging():
        snap = json.loads(_get(f"{base}/stats")[1])["staging"]
        for k in fields:
            assert isinstance(snap[k], int) and not isinstance(snap[k], bool), k
            assert snap[k] >= 0, k
        return snap

    _post(f"{base}/predict", _jpeg(rng))
    first = staging()
    _post(f"{base}/predict", _jpeg(rng))  # other pixels, the same shape
    second = staging()
    assert second["slab_acquires_total"] > first["slab_acquires_total"]
    assert second["slab_allocs_total"] == first["slab_allocs_total"]
    assert second["slab_allocs_total"] <= second["slab_acquires_total"]
    # Every answer is back, so nothing is out and the pool is at its floor.
    assert second["slabs_out"] == 0 and second["slabs_out_bytes"] == 0
    assert second["slabs_pooled_bytes"] <= 256 << 20

    parsed = parse_prometheus_text(_get(f"{base}/metrics")[1].decode())
    samples, types = parsed["samples"], parsed["types"]
    assert samples[("tpu_serve_staging_slab_acquires_total", ())] >= second["slab_acquires_total"]
    assert samples[("tpu_serve_staging_slab_allocs_total", ())] == second["slab_allocs_total"]
    assert samples[("tpu_serve_staging_slabs_out", ())] == 0
    assert samples[("tpu_serve_staging_out_bytes", ())] == 0
    assert types["tpu_serve_staging_slab_acquires_total"] == "counter"
    assert types["tpu_serve_staging_slabs_out"] == "gauge"


def test_demo_page(cls_server):
    base, _ = cls_server
    status, body = _get(f"{base}/")
    assert status == 200 and b"/predict" in body


def test_unknown_route_404(cls_server):
    base, _ = cls_server
    try:
        _get(f"{base}/nope")
        assert False
    except urllib.error.HTTPError as e:
        assert e.code == 404


def test_detect_server(request, rng):
    small_ssd_pb = request.getfixturevalue("small_ssd_pb")
    mc = ModelConfig(
        name="small_ssd", pb_path=small_ssd_pb, task="detect", input_size=(96, 96),
        preprocess="inception", dtype="float32",
        output_names=["raw_boxes", "raw_scores", "anchors"],
    )
    cfg = ServerConfig(model=mc, canvas_buckets=(128,), batch_buckets=(8,), max_delay_ms=2.0)
    engine = InferenceEngine(cfg)
    batcher = Batcher(engine, max_batch=8, max_delay_ms=2.0)
    batcher.start()
    app = App(engine, batcher, cfg)
    srv = make_http_server(app, "127.0.0.1", 0)
    port = srv.server_address[1]
    threading.Thread(target=srv.serve_forever, daemon=True).start()
    try:
        status, resp = _post(f"http://127.0.0.1:{port}/predict", _jpeg(rng, 100, 100))
        assert status == 200
        assert "detections" in resp and resp["num_detections"] == len(resp["detections"])
        if resp["detections"]:
            d = resp["detections"][0]
            assert set(d) == {"box", "class", "label", "score"}
            assert len(d["box"]) == 4
    finally:
        srv.shutdown()
        batcher.stop()


def test_predict_routes_by_model_real_engine(cls_server, rng):
    """Multi-model registry over a REAL engine: two registry entries (the
    engine adopted under two names, each with its OWN batcher — the
    per-model isolation unit), routed by /predict?model=, listed by
    GET /models, labeled in /metrics."""
    import dataclasses

    from tensorflow_web_deploy_tpu.serving.http import shutdown_gracefully
    from tensorflow_web_deploy_tpu.serving.registry import ModelRegistry
    from tensorflow_web_deploy_tpu.utils.metrics import parse_prometheus_text

    _, engine = cls_server
    cfg = engine.cfg
    reg = ModelRegistry(cfg, default_model="small_cls")
    b1 = Batcher(engine, max_batch=8, max_delay_ms=5.0, name="small_cls")
    b1.start()
    b2 = Batcher(engine, max_batch=8, max_delay_ms=5.0, name="alias")
    b2.start()
    reg.adopt("small_cls", engine, b1, cfg.model)
    reg.adopt("alias", engine, b2, dataclasses.replace(cfg.model, name="alias"))
    app = App.from_registry(reg, cfg)
    srv = make_http_server(app, "127.0.0.1", 0)
    port = srv.server_address[1]
    threading.Thread(target=srv.serve_forever, daemon=True).start()
    base = f"http://127.0.0.1:{port}"
    jpeg = _jpeg(rng)
    try:
        status, resp = _post(f"{base}/predict", jpeg)
        assert status == 200 and resp["model"] == "small_cls"
        status, resp2 = _post(f"{base}/predict?model=alias", jpeg)
        assert status == 200 and resp2["model"] == "alias"
        # Same engine behind both names → identical predictions.
        assert resp2["predictions"] == resp["predictions"]
        try:
            _post(f"{base}/predict?model=ghost", jpeg)
            assert False, "expected 404"
        except urllib.error.HTTPError as e:
            assert e.code == 404

        _, body = _get(f"{base}/models")
        doc = json.loads(body)
        assert set(doc["models"]) == {"small_cls", "alias"}
        assert doc["default"] == "small_cls"
        assert doc["models"]["alias"]["versions"][0]["state"] == "SERVING"
        assert doc["models"]["alias"]["versions"][0]["stats"]["requests_total"] >= 1

        _, body = _get(f"{base}/metrics")
        samples = parse_prometheus_text(body.decode())["samples"]
        assert samples[("tpu_serve_model_inferences_total",
                        (("model", "alias"), ("version", "1")))] >= 1
        assert samples[("tpu_serve_model_state",
                        (("model", "small_cls"), ("state", "SERVING"),
                         ("version", "1")))] == 1
    finally:
        srv.shutdown()
        shutdown_gracefully(srv, reg, grace_s=3.0)


def test_build_server_multi_model_validation():
    """The CLI fan-out validates BEFORE any engine builds: duplicate model
    names, an unknown --default-model, and single-model-only knobs with
    repeated --model all exit with a message instead of booting half a
    registry."""
    import server as server_mod

    args = server_mod.parse_args(["--model", "inception_v3",
                                  "--model", "inception_v3"])
    with pytest.raises(SystemExit, match="duplicate model name"):
        server_mod.build_server(args)

    args = server_mod.parse_args(["--model", "inception_v3",
                                  "--default-model", "nope"])
    with pytest.raises(SystemExit, match="not among the loaded models"):
        server_mod.build_server(args)

    args = server_mod.parse_args(["--model", "inception_v3",
                                  "--model", "resnet50", "--ckpt", "/x"])
    with pytest.raises(SystemExit, match="exactly one"):
        server_mod.build_server(args)

    a = server_mod.parse_args(["--model", "a", "--model", "b",
                               "--default-model", "b"])
    assert a.model == ["a", "b"] and a.default_model == "b"
    assert server_mod.parse_args([]).model is None  # default applied later


def test_detect_server_preset_shape(request, rng):
    """Regression for the ssd_mobilenet frozen-graph preset crash (VERDICT
    round 5, Weak #1): the preset used to set no ``output_names``, the
    freeze wraps the semantic identities in anonymous ``Identity`` sinks,
    and the engine's detect branch died at build with
    ``KeyError: 'raw_boxes'``. This builds the config EXACTLY the way the
    preset does — ``model_config("ssd_mobilenet")`` with only the pb path /
    size swapped for the small fixture graph — so a preset regression
    crashes here, at engine build, not in production."""
    import dataclasses

    from tensorflow_web_deploy_tpu.utils.config import model_config

    preset = model_config("ssd_mobilenet")
    assert preset.output_names == ["raw_boxes", "raw_scores", "anchors"], (
        "the ssd preset must name its semantic outputs explicitly — "
        "inferred sinks are the freeze's anonymous Identity wrappers"
    )
    small_ssd_pb = request.getfixturevalue("small_ssd_pb")
    mc = dataclasses.replace(
        preset, pb_path=small_ssd_pb, input_size=(96, 96), dtype="float32",
    )
    cfg = ServerConfig(model=mc, canvas_buckets=(128,), batch_buckets=(8,))
    engine = InferenceEngine(cfg)  # KeyError: 'raw_boxes' before the fix
    canvases = np.zeros((2, 128, 128, 3), np.uint8)
    hws = np.full((2, 2), 128, np.int32)
    boxes, scores, classes, num = engine.run_batch(canvases, hws)
    assert boxes.shape[0] == 2 and boxes.shape[-1] == 4
    assert np.all(np.isfinite(boxes)) and np.all(np.isfinite(scores))


def test_body_too_large_413(cls_server, rng):
    """Oversized uploads are rejected from the declared Content-Length,
    before any buffering — exercised at the WSGI layer so the test doesn't
    ship tens of MB through a socket."""
    base, engine = cls_server
    cfg = engine.cfg
    app = App(engine, None, cfg)  # batcher unreachable: 413 happens first

    captured = {}

    def start_response(status, headers):
        captured["status"] = status

    environ = {
        "PATH_INFO": "/predict",
        "REQUEST_METHOD": "POST",
        "CONTENT_LENGTH": str(int(cfg.max_body_mb * 1e6) + 1),
        "CONTENT_TYPE": "image/jpeg",
        "wsgi.input": io.BytesIO(b"x" * 128),  # under-declared stream
        "QUERY_STRING": "",
    }
    body = b"".join(app(environ, start_response))
    assert captured["status"].startswith("413")
    assert b"cap" in body

    # A small declared body passes the cap; with no batcher attached the
    # app then fails fast with 503 (previously it would have read the body
    # and crashed at submit) — the cap check demonstrably ran first.
    environ["CONTENT_LENGTH"] = "64"
    environ["wsgi.input"] = io.BytesIO(_jpeg(rng)[:64])
    app(environ, start_response)
    assert captured["status"].startswith("503")


def test_bad_topk_param_400(cls_server, rng):
    base, _ = cls_server
    try:
        _post(f"{base}/predict?topk=abc", _jpeg(rng))
        assert False, "expected 400"
    except urllib.error.HTTPError as e:
        assert e.code == 400


def test_negative_topk_clamped(cls_server, rng):
    """topk=-1 must not slice labels from the end (which would return
    nearly the whole class vector); it clamps to an empty result."""
    base, _ = cls_server
    status, resp = _post(f"{base}/predict?topk=-1", _jpeg(rng))
    assert status == 200
    assert resp["predictions"] == []


def test_percent_encoded_and_duplicate_query_params(cls_server, rng):
    """Query parsing goes through parse_qs: percent-encoded values decode
    (%33 → "3") and the last duplicate key wins — the hand-rolled splitter
    mis-parsed both."""
    base, _ = cls_server
    status, resp = _post(f"{base}/predict?topk=%33", _jpeg(rng))
    assert status == 200
    assert len(resp["predictions"]) == 3

    status, resp = _post(f"{base}/predict?topk=1&topk=2", _jpeg(rng))
    assert status == 200
    assert len(resp["predictions"]) == 2


def test_keepalive_two_predicts_one_socket(cls_server, rng):
    """Tier-1 keep-alive contract through the real app: two sequential
    /predict calls ride one TCP connection."""
    import http.client
    from urllib.parse import urlsplit

    base, _ = cls_server
    u = urlsplit(base)
    conn = http.client.HTTPConnection(u.hostname, u.port, timeout=120)
    jpeg = _jpeg(rng)
    try:
        conn.request("POST", "/predict", body=jpeg, headers={"Content-Type": "image/jpeg"})
        r1 = conn.getresponse()
        body1 = json.loads(r1.read())
        assert r1.status == 200 and not r1.will_close
        sock = conn.sock
        conn.request("POST", "/predict", body=jpeg, headers={"Content-Type": "image/jpeg"})
        r2 = conn.getresponse()
        body2 = json.loads(r2.read())
        assert r2.status == 200
        assert conn.sock is sock  # same connection, no reconnect
        assert body1["predictions"] == body2["predictions"]
    finally:
        conn.close()


def test_multipart_text_field_before_file(cls_server, rng):
    boundary = "bnd7"
    base, _ = cls_server
    jpeg = _jpeg(rng)
    body = (
        f"--{boundary}\r\n"
        'Content-Disposition: form-data; name="comment"\r\n\r\n'
        "a text field\r\n"
        f"--{boundary}\r\n"
        'Content-Disposition: form-data; name="image"; filename="t.jpg"\r\n'
        "Content-Type: image/jpeg\r\n\r\n"
    ).encode() + jpeg + f"\r\n--{boundary}--\r\n".encode()
    status, resp = _post(
        f"{base}/predict", body, ctype=f"multipart/form-data; boundary={boundary}"
    )
    assert status == 200 and len(resp["predictions"]) == 5


def test_predict_multipart_multiple_files(cls_server, rng):
    """Several file parts in one request → {"results": [...]} in upload
    order, each entry identical to what the single-image call returns for
    that image (the request is just a client-assembled batch)."""
    base, _ = cls_server
    jpegs = [_jpeg(rng) for _ in range(3)]

    singles = []
    for j in jpegs:
        status, resp = _post(f"{base}/predict", j, ctype="image/jpeg")
        assert status == 200
        singles.append(resp["predictions"])

    boundary = "multibound7"
    parts = b"".join(
        (
            f"--{boundary}\r\n"
            f'Content-Disposition: form-data; name="image{i}"; filename="t{i}.jpg"\r\n'
            "Content-Type: image/jpeg\r\n\r\n"
        ).encode()
        + j
        + b"\r\n"
        for i, j in enumerate(jpegs)
    )
    body = parts + f"--{boundary}--\r\n".encode()
    status, resp = _post(
        f"{base}/predict", body, ctype=f"multipart/form-data; boundary={boundary}"
    )
    assert status == 200
    assert len(resp["results"]) == 3
    for got, want in zip(resp["results"], singles):
        assert [p["index"] for p in got["predictions"]] == [p["index"] for p in want]
        for g, w in zip(got["predictions"], want):
            assert abs(g["score"] - w["score"]) < 1e-5


def test_predict_multipart_rejects_undecodable_part(cls_server, rng):
    base, _ = cls_server
    boundary = "multibound8"
    good = _jpeg(rng)
    body = (
        (
            f"--{boundary}\r\n"
            'Content-Disposition: form-data; name="a"; filename="a.jpg"\r\n\r\n'
        ).encode()
        + good
        + (
            f"\r\n--{boundary}\r\n"
            'Content-Disposition: form-data; name="b"; filename="b.jpg"\r\n\r\n'
            "this is not an image"
            f"\r\n--{boundary}--\r\n"
        ).encode()
    )
    try:
        _post(f"{base}/predict", body, ctype=f"multipart/form-data; boundary={boundary}")
        assert False, "expected 400"
    except urllib.error.HTTPError as e:
        assert e.code == 400
        # names the offending upload, not just an index
        assert "b.jpg" in json.loads(e.read())["error"]


def test_multipart_payload_trailing_newline_preserved():
    """The parser removes exactly the framing CRLF — file content that
    itself ends in 0x0A/0x0D (BMP/TIFF/WebP can) must survive byte-exact."""
    from tensorflow_web_deploy_tpu.serving.http import _parse_multipart_files

    payload = b"\x89IMG-DATA\x0a\x0a"
    boundary = "pb1"
    body = (
        (
            f"--{boundary}\r\n"
            'Content-Disposition: form-data; name="f"; filename="x.bin"\r\n\r\n'
        ).encode()
        + payload
        + f"\r\n--{boundary}--\r\n".encode()
    )
    files = _parse_multipart_files(body, f"multipart/form-data; boundary={boundary}")
    assert files == [("x.bin", payload)]


def test_stats_tracing_block(cls_server, rng):
    """/stats carries the cumulative per-stage span aggregates the loadgen
    stage-attribution diff consumes."""
    base, _ = cls_server
    _post(f"{base}/predict", _jpeg(rng))
    _, body = _get(f"{base}/stats")
    tracing = json.loads(body)["tracing"]
    assert tracing["e2e"]["count"] >= 1
    for key in ("count", "total_ms", "mean_ms", "p50_ms", "p99_ms"):
        assert key in tracing["e2e"]
    assert "image_decode" in tracing["stages"]
    assert "device_execute" in tracing["stages"]
    assert tracing["requests_by_status"].get("2xx", 0) >= 1


def test_metrics_prometheus_real_engine(cls_server, rng):
    """GET /metrics against the REAL engine parses as text exposition and
    its histogram counts agree with requests_total; the staging-pool and
    batcher gauges ride along."""
    from tensorflow_web_deploy_tpu.utils.metrics import parse_prometheus_text

    base, _ = cls_server
    _post(f"{base}/predict", _jpeg(rng))
    status, body = _get(f"{base}/metrics")
    assert status == 200
    parsed = parse_prometheus_text(body.decode())  # raises if malformed
    samples = parsed["samples"]
    requests_total = sum(
        v for (name, _), v in samples.items() if name == "tpu_serve_requests_total"
    )
    assert requests_total == samples[
        ("tpu_serve_request_duration_seconds_bucket", (("le", "+Inf"),))
    ] > 0
    assert ("tpu_serve_staging_slab_allocs_total", ()) in samples
    assert ("tpu_serve_inferences_total", ()) in samples
    assert parsed["types"]["tpu_serve_stage_duration_seconds"] == "histogram"


def test_span_stages_cover_end_to_end_latency(cls_server, rng):
    """Acceptance: a request served through the real batching path yields a
    span with ≥ 8 named stages whose summed durations land within 20% of
    the reported end-to-end latency (the stages tile the request, they are
    not a grab-bag of overlapping timers)."""
    import http.client
    from urllib.parse import urlsplit

    base, _ = cls_server
    u = urlsplit(base)
    conn = http.client.HTTPConnection(u.hostname, u.port, timeout=120)
    try:
        conn.request("POST", "/predict", body=_jpeg(rng),
                     headers={"Content-Type": "image/jpeg"})
        r = conn.getresponse()
        assert r.status == 200
        trace_id = r.getheader("X-Trace-Id")
        r.read()
    finally:
        conn.close()
    assert trace_id

    _, body = _get(f"{base}/debug/slow")
    spans = json.loads(body)["slowest"]
    mine = [s for s in spans if s["trace_id"] == trace_id]
    assert mine, "the request's span must be in the flight recorder"
    span = mine[0]
    stages = span["stages_ms"]
    assert len(stages) >= 8, f"expected >= 8 stages, got {sorted(stages)}"
    assert {"http_read", "body_read", "image_decode", "queue_wait",
            "staging_write", "device_transfer", "device_execute",
            "device_d2h", "postprocess", "serialize"} <= set(stages)
    total = span["total_ms"]
    assert total > 0
    assert sum(stages.values()) >= 0.8 * total, (stages, total)
    # stages can never sum past the wall time by more than rounding slack
    assert sum(stages.values()) <= total * 1.2 + 1.0, (stages, total)


def test_predict_decodes_into_leased_slab_row(cls_server, rng, monkeypatch):
    """The re-ordered request path end-to-end: /predict hands the native
    decoder a LEASED SLAB ROW as its destination (a view into shared slab
    memory, never a fresh allocation) — the instrumented proof that the
    JPEG fast path's single host copy is the decode itself."""
    from tensorflow_web_deploy_tpu import native

    if not native.available():
        pytest.skip("no compiler/libjpeg for the native extension")
    seen = []
    real = native.decode_into_row

    def spy(data, row, canvas, wire, **kw):
        seen.append((row.base is not None, row.flags["OWNDATA"]))
        return real(data, row, canvas, wire, **kw)

    monkeypatch.setattr(native, "decode_into_row", spy)
    base, _ = cls_server
    status, resp = _post(f"{base}/predict", _jpeg(rng))
    assert status == 200 and resp["predictions"]
    assert seen, "the lease path must route decodes through decode_into_row"
    is_view, owns = seen[0]
    assert is_view and not owns  # slab view, not a scratch allocation


def test_response_cache_etag_and_304_real_engine(cls_server, rng):
    """Satellite regression: ETag (= response digest) on /predict and
    ``If-None-Match`` → 304, through the REAL decode-into-slab path — the
    content digest is computed from the leased slab row after the native
    decode (PIL-fallback canvas when the extension is unavailable), so a
    repeat upload hits the cache without touching the device."""
    import dataclasses
    import http.client
    from urllib.parse import urlsplit

    from tensorflow_web_deploy_tpu.serving.http import shutdown_gracefully
    from tensorflow_web_deploy_tpu.utils.metrics import parse_prometheus_text

    _, engine = cls_server
    cfg = dataclasses.replace(engine.cfg, cache_bytes=32 << 20)
    batcher = Batcher(engine, max_batch=8, max_delay_ms=5.0)
    batcher.start()
    app = App(engine, batcher, cfg)
    srv = make_http_server(app, "127.0.0.1", 0)
    threading.Thread(target=srv.serve_forever, daemon=True).start()
    u = urlsplit(f"http://127.0.0.1:{srv.server_address[1]}")

    def post(body, headers=None, path="/predict"):
        conn = http.client.HTTPConnection(u.hostname, u.port, timeout=120)
        try:
            conn.request("POST", path, body=body,
                         headers={"Content-Type": "image/jpeg",
                                  **(headers or {})})
            r = conn.getresponse()
            data = r.read()
            return (r.status, json.loads(data) if data else None,
                    {k.lower(): v for k, v in r.getheaders()})
        finally:
            conn.close()

    try:
        jpeg_a, jpeg_b = _jpeg(rng), _jpeg(rng)
        status, resp, hdr = post(jpeg_a)
        assert status == 200 and hdr["x-cache"] == "miss"
        etag = hdr["etag"]
        assert etag.startswith('"') and etag.endswith('"')

        status2, resp2, hdr2 = post(jpeg_a)
        assert status2 == 200 and hdr2["x-cache"] == "hit"
        assert hdr2["etag"] == etag
        assert resp2["predictions"] == resp["predictions"]

        status3, resp3, hdr3 = post(jpeg_a, headers={"If-None-Match": etag})
        assert status3 == 304 and resp3 is None
        assert hdr3["etag"] == etag and hdr3["content-length"] == "0"

        # Distinct content = distinct cache key: a fresh miss. (This
        # random-weight fixture model emits a uniform softmax, so two
        # different noise images legitimately share a RESPONSE digest —
        # the ETag validates response content, the cache key validates
        # request content.)
        status4, _, hdr4 = post(jpeg_b)
        assert status4 == 200 and hdr4["x-cache"] == "miss"

        # Content sensitivity of the response digest: a different topk
        # changes the payload, so its ETag (and cache key) must differ.
        status5, resp5, hdr5 = post(jpeg_a, path="/predict?topk=3")
        assert status5 == 200 and hdr5["x-cache"] == "miss"
        assert hdr5["etag"] != etag and len(resp5["predictions"]) == 3

        stats = app.cache.stats()
        assert stats["hits_total"] >= 2 and stats["misses_total"] >= 2
        conn = http.client.HTTPConnection(u.hostname, u.port, timeout=30)
        conn.request("GET", "/metrics")
        samples = parse_prometheus_text(
            conn.getresponse().read().decode()
        )["samples"]
        conn.close()
        assert samples[("tpu_serve_cache_hits_total", ())] >= 2
    finally:
        shutdown_gracefully(srv, batcher, grace_s=3.0)


def test_predict_single_file_batch_shape(cls_server, rng):
    """?batch=1 forces the {"results": [...]} schema even for one image, so
    batch clients keep a stable shape at n=1."""
    base, _ = cls_server
    boundary = "single1"
    body = (
        f"--{boundary}\r\n"
        'Content-Disposition: form-data; name="image"; filename="t.jpg"\r\n\r\n'
    ).encode() + _jpeg(rng) + f"\r\n--{boundary}--\r\n".encode()
    status, resp = _post(
        f"{base}/predict?batch=1", body,
        ctype=f"multipart/form-data; boundary={boundary}",
    )
    assert status == 200
    assert len(resp["results"]) == 1
    assert resp["results"][0]["predictions"]
