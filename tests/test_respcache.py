"""Content-addressed response cache: digest determinism, the byte-budgeted
LRU, single-flight dedup (leader/waiter/abort), version-gated invalidation
— and the HTTP-level acceptance pieces: X-Cache/ETag/304 on /predict,
coalesced concurrent identical requests, and the hot-swap-under-load
zero-stale-responses run.

All on mock engines (no jax): the cache is engine-agnostic by design; the
real-engine integration (decode-into-slab digest path, ETag on a real
model's responses) rides through test_server.py.
"""

import http.client
import json
import threading
import time

import numpy as np
import pytest

from tensorflow_web_deploy_tpu.serving.http import (
    App, make_http_server, shutdown_gracefully,
)
from tensorflow_web_deploy_tpu.serving.registry import ModelRegistry
from tensorflow_web_deploy_tpu.serving.respcache import (
    CacheRetired, ResponseCache, canvas_digest, make_key, payload_etag,
    stage_input_digest, upload_digest,
)
from tensorflow_web_deploy_tpu.utils.config import ModelConfig, ServerConfig


class _Mesh:
    devices = np.zeros(1)


class MockEngine:
    """Classify-shaped engine whose answers identify the engine instance
    (score == ``self.score``) and whose ``prepare_bytes`` derives the
    canvas from the upload bytes — distinct uploads get distinct content
    digests, identical uploads collide, exactly like real decoded images.
    ``fetch_gate`` (optional Event) holds every fetch open — the lever for
    deterministic coalescing tests."""

    batch_buckets = (8,)
    max_batch = 8
    mesh = _Mesh()

    def __init__(self, score=0.5, fetch_gate=None, warm_gate=None):
        self.score = score
        self.fetch_gate = fetch_gate
        self.warm_gate = warm_gate
        self.dispatches = 0

    def warmup(self):
        if self.warm_gate is not None:
            assert self.warm_gate.wait(timeout=30), "warm gate never opened"

    def close(self):
        pass

    def healthcheck(self):
        return True

    def prepare_bytes(self, data):
        if not data or data == b"not an image":
            raise ValueError("undecodable")
        v = sum(data) % 251
        return np.full((8, 8, 3), v, np.uint8), (8, 8), (8, 8)

    def dispatch_batch(self, canvases, hws):
        self.dispatches += 1
        return len(canvases)

    def fetch_outputs(self, handle):
        if self.fetch_gate is not None:
            assert self.fetch_gate.wait(timeout=30), "fetch gate never opened"
        n = handle
        scores = np.full((n, 5), self.score, np.float32)
        idx = np.tile(np.arange(5, dtype=np.int32), (n, 1))
        return scores, idx


def _mc(name="m1"):
    return ModelConfig(name=name, source="native", task="classify")


def _cfg(cache_bytes=1 << 20, name="m1"):
    return ServerConfig(model=_mc(name), max_batch=8, max_delay_ms=1.0,
                        request_timeout_s=10.0, drain_grace_s=5.0,
                        cache_bytes=cache_bytes)


def _payload(i=0):
    return {"predictions": [{"label": f"class_{i}", "index": i, "score": 0.5}]}


# ------------------------------------------------------------------ digest


def test_canvas_digest_deterministic_and_content_sensitive(rng):
    canvas = (rng.rand(16, 16, 3) * 255).astype(np.uint8)
    d1 = canvas_digest(canvas, (12, 9))
    assert d1 == canvas_digest(canvas.copy(), (12, 9)), (
        "same bytes + hw must digest identically across buffers"
    )
    flipped = canvas.copy()
    flipped[3, 4, 1] ^= 1
    assert canvas_digest(flipped, (12, 9)) != d1, "one-pixel change must miss"
    assert canvas_digest(canvas, (12, 10)) != d1, (
        "hw rides the digest: genuine black edges vs padding must differ"
    )
    # Non-contiguous views (a slab row sliced oddly) digest like their copy.
    view = canvas[:, ::2]
    assert canvas_digest(view, (8, 8)) == canvas_digest(
        np.ascontiguousarray(view), (8, 8)
    )


UPLOAD = b"\xff\xd8 the bytes of one upload \xff\xd9"
# (data, buckets, wire) that must not share a digest with
# (UPLOAD, (256, 512), "ragged"): another upload, or the same one where the
# device would see other pixels for it.
OTHER_UPLOADS = {
    "one_byte_changed": (UPLOAD[:5] + b"T" + UPLOAD[6:], (256, 512), "ragged"),
    "one_byte_more": (UPLOAD + b"\0", (256, 512), "ragged"),
    "empty": (b"", (256, 512), "ragged"),
    "narrowed_bucket_set": (UPLOAD, (256,), "ragged"),
    "another_bucket_set": (UPLOAD, (256, 1024), "ragged"),
    "buckets_that_spell_alike": (UPLOAD, (25, 6512), "ragged"),
    "wire_rgb": (UPLOAD, (256, 512), "rgb"),
    "wire_yuv420": (UPLOAD, (256, 512), "yuv420"),
    "no_leases": (UPLOAD, (256, 512), None),
    # An upload whose tail spells another request's context, and one whose
    # head does: the context is framed, so neither passes for it.
    "tail_spells_a_context": (UPLOAD + b"|256|", (512,), "ragged"),
    "head_spells_a_context": (b"ragged|256,512|" + UPLOAD, (), None),
}


@pytest.mark.parametrize("other", list(OTHER_UPLOADS))
def test_upload_digest_tells_apart(other):
    base = upload_digest(UPLOAD, (256, 512), "ragged")
    assert len(base) == 32  # blake2b-128, as hex
    assert upload_digest(*OTHER_UPLOADS[other]) != base


@pytest.mark.parametrize("same", [
    (bytearray(UPLOAD), (256, 512), "ragged"),
    (memoryview(UPLOAD), [256, 512], "ragged"),
    (UPLOAD, (np.int64(256), np.int64(512)), "ragged"),
], ids=["bytearray", "memoryview_and_list", "numpy_ints"])
def test_upload_digest_is_of_the_content_not_of_its_container(same):
    assert upload_digest(*same) == upload_digest(UPLOAD, (256, 512), "ragged")


@pytest.mark.parametrize("bulk", [False, True], ids=["interactive", "bulk"])
@pytest.mark.parametrize("outcome", ["lead", "wait", "hit"])
def test_digest_bytes_total_counts_what_every_lookup_hashed(outcome, bulk):
    cache = ResponseCache(1 << 20)
    key = make_key("m", 1, upload_digest(UPLOAD, (256,), "rgb"), 5)
    assert cache.stats()["digest_bytes_total"] == 0
    want = 0
    if outcome != "lead":
        _, flight = cache.begin(key, "m", digest_bytes=len(UPLOAD))
        want = len(UPLOAD)
        if outcome == "hit":
            cache.complete(flight, _payload())
    kind, _ = cache.begin(key, "m", bulk=bulk, digest_bytes=len(UPLOAD))
    assert kind == outcome
    assert cache.stats()["digest_bytes_total"] == want + len(UPLOAD)
    # A key the caller did not hash for (a pipeline stage's) counts none.
    cache.begin(make_key("m", 1, "stage-input", 5), "m", bulk=bulk)
    assert cache.stats()["digest_bytes_total"] == want + len(UPLOAD)


def test_payload_etag_stable_and_version_sensitive():
    p = _payload()
    assert payload_etag(p, "m", 1) == payload_etag(json.loads(json.dumps(p)), "m", 1)
    assert payload_etag(p, "m", 1) != payload_etag(p, "m", 2)


def test_dag_stage_key_carries_model_version_dtype_and_stage_input():
    """Regression for the pipeline-DAG key contract: a downstream stage's
    cache key must include (model, version, dtype, stage-input digest) —
    the stage-input digest folds the request digest together with the
    UPSTREAM stage's result, so a changed detection set re-keys stage 2
    while a classifier hot-swap (version bump) invalidates ONLY stage 2."""
    s1 = {"boxes": [[0.1, 0.2, 0.5, 0.6]], "scores": [0.9], "classes": [3],
          "labels": ["cat"], "num": 1}
    d = stage_input_digest("imgdigest", s1)
    # Deterministic across dict insertion order (canonical payload form).
    reordered = json.loads(json.dumps(s1, sort_keys=True))
    assert stage_input_digest("imgdigest", reordered) == d
    # Sensitive to the upstream result AND to the original request.
    bumped = json.loads(json.dumps(s1))
    bumped["boxes"][0][0] = 0.1000001
    assert stage_input_digest("imgdigest", bumped) != d
    assert stage_input_digest("otherimg", s1) != d

    key = make_key("cls", 4, d, 5, "int8")
    assert key[0] == "cls" and key[1] == 4
    assert d in key and "int8" in key
    # Each identity axis re-keys independently.
    assert make_key("cls", 5, d, 5, "int8") != key          # version (swap)
    assert make_key("cls", 4, d, 5, "float32") != key       # serving tier
    assert make_key("cls", 4, stage_input_digest("imgdigest", bumped),
                    5, "int8") != key                       # stage input
    assert make_key("det", 4, d, 5, "int8") != key          # stage model
    assert make_key("cls", 4, d, 3, "int8") != key          # topk slot


# ------------------------------------------------------------- LRU budget


def _fill(cache, model, version, i, payload=None):
    key = make_key(model, version, f"digest{i}", 5)
    kind, flight = cache.begin(key, model)
    assert kind == "lead"
    cache.complete(flight, payload or _payload(i))
    return key


def test_lru_byte_budget_evicts_least_recently_hit():
    entry_bytes = len(json.dumps(_payload(0), separators=(",", ":")))
    cache = ResponseCache(entry_bytes * 3 + 2)  # room for exactly 3 entries
    keys = [_fill(cache, "m", 1, i) for i in range(3)]
    assert cache.stats()["entries"] == 3
    # Touch key 0 so key 1 becomes the LRU victim.
    assert cache.begin(keys[0], "m")[0] == "hit"
    _fill(cache, "m", 1, 99)
    s = cache.stats()
    assert s["entries"] == 3 and s["evictions_total"] == 1
    assert s["bytes"] <= cache.max_bytes
    assert cache.begin(keys[1], "m")[0] == "lead", "LRU entry must be gone"
    assert cache.begin(keys[0], "m")[0] == "hit", "recently-hit entry survives"


def test_oversized_payload_never_cached_and_disabled_cache_stores_nothing():
    tiny = ResponseCache(8)  # smaller than any payload
    key = _fill(tiny, "m", 1, 0)
    assert tiny.begin(key, "m")[0] == "lead"
    assert tiny.stats()["entries"] == 0

    off = ResponseCache(0)
    assert not off.enabled
    key = _fill(off, "m", 1, 0)
    assert off.stats()["entries"] == 0 and off.bytes == 0
    assert off.begin(key, "m")[0] == "lead"


# ----------------------------------------------------------- single flight


def test_single_flight_leader_waiter_hit_counters():
    cache = ResponseCache(1 << 20)
    key = make_key("m", 1, "d0", 5)
    kind, flight = cache.begin(key, "m")
    assert kind == "lead"
    kind2, flight2 = cache.begin(key, "m")
    assert kind2 == "wait" and flight2 is flight

    got = []
    t = threading.Thread(
        target=lambda: got.append(flight2.future.result(timeout=10)),
        daemon=True,
    )
    t.start()
    etag = cache.complete(flight, _payload())
    t.join(timeout=10)
    assert got and got[0] == (_payload(), etag)

    kind3, entry = cache.begin(key, "m")
    assert kind3 == "hit" and entry.etag == etag
    s = cache.stats()
    assert (s["hits_total"], s["misses_total"], s["coalesced_total"]) == (1, 1, 1)
    assert s["inflight"] == 0
    assert s["per_model"]["m"]["hits"] == 1
    assert s["hit_rate"] is not None


def test_single_flight_abort_fails_waiters():
    cache = ResponseCache(1 << 20)
    key = make_key("m", 1, "d1", 5)
    _, flight = cache.begin(key, "m")
    _, waiter = cache.begin(key, "m")
    cache.abort(flight, RuntimeError("leader died"))
    with pytest.raises(RuntimeError, match="leader died"):
        waiter.future.result(timeout=5)
    # The key is free again: the next request leads a fresh computation.
    assert cache.begin(key, "m")[0] == "lead"


def test_invalidate_drops_entries_and_retires_flights():
    cache = ResponseCache(1 << 20)
    kept = _fill(cache, "m", 2, 7)          # the successor version's entry
    _fill(cache, "m", 1, 0)
    key = make_key("m", 1, "d-inflight", 5)
    _, flight = cache.begin(key, "m")       # v1 computation in flight
    _, waiter = cache.begin(key, "m")

    dropped = cache.invalidate("m", 1)
    assert dropped == 1
    # Coalesced waiters fall through: they see CacheRetired (the HTTP layer
    # retries them against the NEW serving version as a miss).
    with pytest.raises(CacheRetired):
        waiter.future.result(timeout=5)
    # A leader completing AFTER its version retired must not re-insert.
    cache.complete(flight, _payload())
    assert cache.begin(key, "m")[0] == "lead"
    # Other versions are untouched.
    assert cache.begin(kept, "m")[0] == "hit"
    s = cache.stats()
    assert s["invalidations_total"] == 1


# ------------------------------------------------------------- HTTP surface


@pytest.fixture()
def cache_server():
    """Registry-backed mock server with the response cache ENABLED; scores
    encode build order (0.1 * n) so a response proves WHICH version served
    it — the stale-detection primitive."""
    warm_gate = threading.Event()
    warm_gate.set()
    fetch_gate = threading.Event()
    fetch_gate.set()
    counter = {"n": 0}
    engines = []

    def factory(mc):
        counter["n"] += 1
        e = MockEngine(score=round(0.1 * counter["n"], 3),
                       fetch_gate=fetch_gate, warm_gate=warm_gate)
        engines.append(e)
        return e

    cfg = _cfg()
    r = ModelRegistry(cfg, engine_factory=factory, spec_resolver=_mc)
    r.load("m1", wait=True)
    app = App.from_registry(r, cfg)
    srv = make_http_server(app, "127.0.0.1", 0, pool_size=8)
    threading.Thread(target=srv.serve_forever, daemon=True).start()
    yield srv.server_address[1], r, app, warm_gate, fetch_gate, engines
    fetch_gate.set()
    warm_gate.set()
    shutdown_gracefully(srv, r, grace_s=3.0)


def _post(port, body, path="/predict", headers=None, timeout=15):
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=timeout)
    try:
        conn.request("POST", path, body=body,
                     headers={"Content-Type": "image/jpeg", **(headers or {})})
        resp = conn.getresponse()
        data = resp.read()
        return resp.status, (json.loads(data) if data else None), dict(
            (k.lower(), v) for k, v in resp.getheaders()
        )
    finally:
        conn.close()


def test_http_miss_then_hit_with_etag_and_304(cache_server):
    port, r, app, *_ = cache_server
    status, resp, hdr = _post(port, b"img-a")
    assert status == 200 and hdr["x-cache"] == "miss"
    etag = hdr["etag"]
    assert etag.startswith('"') and etag.endswith('"')

    status2, resp2, hdr2 = _post(port, b"img-a")
    assert status2 == 200 and hdr2["x-cache"] == "hit"
    assert hdr2["etag"] == etag
    assert resp2["predictions"] == resp["predictions"]

    # If-None-Match round-trip: the client's copy is current → 304, no body.
    status3, resp3, hdr3 = _post(port, b"img-a", headers={"If-None-Match": etag})
    assert status3 == 304 and resp3 is None
    assert hdr3["etag"] == etag and hdr3["content-length"] == "0"
    # A stale validator still gets the full 200.
    status4, _, hdr4 = _post(port, b"img-a",
                             headers={"If-None-Match": '"deadbeef"'})
    assert status4 == 200 and hdr4["x-cache"] == "hit"

    # Distinct content = distinct cache key: a fresh miss. (The mock
    # engine answers identically for every image, so the RESPONSE digest —
    # the ETag — legitimately matches: ETag validates response content,
    # the cache key validates request content. test_server.py covers
    # distinct-ETags-for-distinct-predictions on a real model.)
    status5, _, hdr5 = _post(port, b"img-b")
    assert status5 == 200 and hdr5["x-cache"] == "miss"
    assert hdr5["etag"] == etag

    stats = app.cache.stats()
    assert stats["hits_total"] >= 2 and stats["misses_total"] >= 2
    assert stats["per_model"]["m1"]["entries"] >= 2


def test_http_stats_and_metrics_carry_cache_block(cache_server):
    from tensorflow_web_deploy_tpu.utils.metrics import parse_prometheus_text

    port, *_ = cache_server
    _post(port, b"img-m")
    _post(port, b"img-m")
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=10)
    conn.request("GET", "/stats")
    snap = json.loads(conn.getresponse().read())
    conn.request("GET", "/metrics")
    text = conn.getresponse().read().decode()
    conn.close()
    c = snap["cache"]
    assert c["enabled"] and c["hits_total"] >= 1 and c["entries"] >= 1
    assert snap["config"]["cache_bytes"] == 1 << 20
    samples = parse_prometheus_text(text)["samples"]
    assert samples[("tpu_serve_cache_hits_total", ())] >= 1
    assert samples[("tpu_serve_cache_bytes", ())] >= 1
    assert samples[("tpu_serve_model_cache_hits_total", (("model", "m1"),))] >= 1


def test_concurrent_identical_requests_coalesce_to_one_dispatch(cache_server):
    """Single-flight acceptance: N concurrent requests for the same content
    key cost ONE device dispatch — the leader computes, everyone else
    coalesces onto its flight and shares the result."""
    port, r, app, _warm, fetch_gate, engines = cache_server
    fetch_gate.clear()  # hold the leader's fetch open
    results = []

    def fire():
        try:
            results.append(_post(port, b"img-coal", timeout=30))
        except Exception as e:  # noqa: BLE001 — a failure IS the signal
            results.append(("exc", repr(e), {}))

    threads = [threading.Thread(target=fire) for _ in range(6)]
    try:
        threads[0].start()
        deadline = time.monotonic() + 10
        while app.cache.stats()["inflight"] < 1:
            assert time.monotonic() < deadline, "leader never took flight"
            time.sleep(0.005)
        for t in threads[1:]:
            t.start()
        deadline = time.monotonic() + 10
        while app.cache.stats()["coalesced_total"] < 5:
            assert time.monotonic() < deadline, (
                f"waiters never coalesced: {app.cache.stats()}"
            )
            time.sleep(0.005)
    finally:
        fetch_gate.set()
    for t in threads:
        t.join(timeout=30)

    assert len(results) == 6
    assert all(s == 200 for s, _, _ in results), results
    bodies = [resp["predictions"] for _, resp, _ in results]
    assert all(b == bodies[0] for b in bodies)
    kinds = sorted(h["x-cache"] for _, _, h in results)
    assert kinds.count("coalesced") == 5 and kinds.count("miss") == 1
    assert engines[0].dispatches == 1, (
        "6 identical concurrent requests must cost exactly one dispatch"
    )


def test_hot_swap_under_load_zero_stale_responses(cache_server):
    """Invalidation acceptance: identical-image (cache-hot) traffic hammers
    /predict while the model hot-swaps. A response is STALE when its
    payload was computed by a different version than it claims (score !=
    0.1 * model_version) or when an old-version result arrives after the
    swap completed (old version UNLOADED). Both counts must be zero, with
    zero failed requests — coalesced waiters caught mid-drain fall
    through to a miss on the new version instead of erroring."""
    port, r, app, warm_gate, _fetch, _engines = cache_server
    stop = threading.Event()
    failures = []
    responses = []  # (t_start, model_version, score)

    def hammer():
        while not stop.is_set():
            t_start = time.monotonic()
            try:
                status, resp, _ = _post(port, b"hot-img", timeout=30)
            except Exception as e:
                failures.append(("exc", repr(e)))
                continue
            if status != 200:
                failures.append((status, resp))
            else:
                responses.append((
                    t_start,
                    resp["model_version"],
                    resp["predictions"][0]["score"],
                ))

    threads = [threading.Thread(target=hammer) for _ in range(4)]
    for t in threads:
        t.start()
    try:
        time.sleep(0.3)  # cache-hot steady state on v1
        assert app.cache.stats()["hits_total"] > 0, "traffic must be cache-hot"
        warm_gate.clear()  # make the swap spend real time in WARMING
        v2 = r.swap("m1")
        r.wait_for(v2, ("WARMING",), timeout=10)
        time.sleep(0.2)  # v1 keeps serving (from cache) during the warmup
        warm_gate.set()
        r.wait_for(v2, ("SERVING",), timeout=10)
        v1 = r._models["m1"][1]
        r.wait_for(v1, ("UNLOADED",), timeout=10)
        t_unloaded = time.monotonic()
        time.sleep(0.3)  # cache-hot steady state on v2
    finally:
        stop.set()
        for t in threads:
            t.join(timeout=30)

    assert not failures, f"requests failed during hot swap: {failures[:5]}"
    # Cross-version cache contamination check: every response's payload
    # must come from the version it claims.
    stale = [
        (v, s) for _, v, s in responses if abs(s - 0.1 * v) > 1e-6
    ]
    assert not stale, f"responses served stale cached payloads: {stale[:5]}"
    # An old-version result for a request STARTED after the swap completed
    # = stale by definition (requests in flight AT the flip legitimately
    # finish against the version they resolved — that is the zero-downtime
    # drain contract, not staleness).
    late_old = [
        (at, v) for at, v, _ in responses if at > t_unloaded and v != 2
    ]
    assert not late_old, f"old-version responses after swap: {late_old[:5]}"
    versions = {v for _, v, _ in responses}
    assert versions == {1, 2}, f"both versions must have served: {versions}"
    # The new version built its own cache entries (hits resumed post-swap).
    per_model = app.cache.stats()["per_model"]["m1"]
    assert per_model["hits"] > 0
    assert any(v == 2 for at, v, _ in responses if at > t_unloaded)


def test_cache_disabled_has_no_headers_and_no_dedup():
    """--cache-bytes 0 baseline: no X-Cache header, every request computes
    (the bench's comparison point), but ETag/304 still work — the response
    digest does not need the cache."""
    cfg = _cfg(cache_bytes=0)
    r = ModelRegistry(cfg, engine_factory=lambda mc: MockEngine(),
                      spec_resolver=_mc)
    r.load("m1", wait=True)
    app = App.from_registry(r, cfg)
    srv = make_http_server(app, "127.0.0.1", 0, pool_size=4)
    threading.Thread(target=srv.serve_forever, daemon=True).start()
    port = srv.server_address[1]
    try:
        status, _, hdr = _post(port, b"img-x")
        assert status == 200 and "x-cache" not in hdr
        etag = hdr["etag"]
        status2, resp2, hdr2 = _post(port, b"img-x",
                                     headers={"If-None-Match": etag})
        assert status2 == 304 and resp2 is None and hdr2["etag"] == etag
        assert app.cache.stats()["entries"] == 0
    finally:
        shutdown_gracefully(srv, r, grace_s=3.0)
