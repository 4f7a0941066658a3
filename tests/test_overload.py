"""Overload engineering (ISSUE 13): deadline-aware admission and the
seal-time dead-row re-check, SLO classes, per-tenant token-bucket quotas
with honest Retry-After, the degradation ladder's rung walk, the
quota-before-starvation-valve precedence on the bulk gate, and the
SIGTERM drain-with-inflight-interactive guarantee.

All on mock engines (no jax): admission runs entirely in the batcher/
HTTP layers, by the same seams the registry threads into adopted
batchers. The closed-loop overload *curves* (goodput at 2x offered
load, shed answer latency) live in ``python bench.py overload``.
"""

import io
import json
import threading
import time

import numpy as np
import pytest

from tensorflow_web_deploy_tpu.serving.batcher import BacklogFull, Batcher
from tensorflow_web_deploy_tpu.serving.http import (
    App, make_http_server, shutdown_gracefully,
)
from tensorflow_web_deploy_tpu.serving.overload import (
    AdmissionController, DeadlineExceeded, Degraded, OTHER_TENANT,
    PressureController, QuotaExceeded, parse_slo_classes,
)
from tensorflow_web_deploy_tpu.utils.config import ModelConfig, ServerConfig


class _Mesh:
    devices = np.zeros(1)


class FastEngine:
    """Instant classify engine whose canvas derives from the upload
    bytes — distinct bodies get distinct content digests (the lever for
    cache hit-vs-miss tests), identical bodies collide (cache hits)."""

    max_batch = 4
    batch_buckets = (4,)
    mesh = _Mesh()

    def __init__(self):
        self.dispatches = 0
        self.images = 0

    def prepare_bytes(self, data):
        if not data:
            raise ValueError("empty")
        v = sum(data) % 251
        return np.full((8, 8, 3), v, np.uint8), (8, 8), (8, 8)

    def dispatch_batch(self, canvases, hws):
        self.dispatches += 1
        self.images += len(canvases)
        return len(canvases)

    def fetch_outputs(self, handle):
        n = handle
        return (np.zeros((n, 5), np.float32),
                np.tile(np.arange(5, dtype=np.int32), (n, 1)))


class WedgeEngine(FastEngine):
    """FastEngine whose fetch blocks on an event — the device wedge that
    builds real backlog behind pipeline depth 1."""

    def __init__(self):
        super().__init__()
        self.release = threading.Event()

    def fetch_outputs(self, handle):
        assert self.release.wait(timeout=15), "wedge never released"
        return super().fetch_outputs(handle)


def _canvas(tag=1):
    return np.full((8, 8, 3), tag, np.uint8)


def _post(app, body=b"\xff\xd8fakejpeg", qs="", headers=None):
    """WSGI-direct POST /predict with optional query string and extra
    HTTP_* headers; returns (status, headers-dict, body-bytes)."""
    captured = {}

    def start_response(status, hdrs):
        captured["status"] = status
        captured["headers"] = dict(hdrs)

    environ = {
        "REQUEST_METHOD": "POST",
        "PATH_INFO": "/predict",
        "QUERY_STRING": qs,
        "CONTENT_TYPE": "application/octet-stream",
        "CONTENT_LENGTH": str(len(body)),
        "wsgi.input": io.BytesIO(body),
    }
    for k, v in (headers or {}).items():
        environ["HTTP_" + k.upper().replace("-", "_")] = v
    resp = b"".join(app(environ, start_response))
    return captured["status"], captured["headers"], resp


def _cfg(**kw):
    kw.setdefault("model", ModelConfig(name="mini", source="native"))
    kw.setdefault("request_timeout_s", 20.0)
    kw.setdefault("cache_bytes", 0)
    return ServerConfig(**kw)


# ------------------------------------------------------------ spec parsing


def test_parse_slo_classes_defaults_and_fallback():
    assert parse_slo_classes("interactive=1000,batch=10000") == {
        "interactive": 1.0, "batch": 10.0}
    assert parse_slo_classes(None) == {"interactive": 1.0, "batch": 10.0}
    # Malformed entries drop; an all-garbage spec degrades to defaults
    # instead of crashing boot.
    assert parse_slo_classes("fast=50,oops=banana") == {"fast": 0.05}
    assert parse_slo_classes("oops=banana,=,") == {
        "interactive": 1.0, "batch": 10.0}


def test_parse_rungs_hysteresis_and_fallback():
    rungs = PressureController.parse_rungs("0.5:0.3,0.9:0.7")
    assert rungs == [(0.5, 0.3), (0.9, 0.7)]
    # exit > enter is clamped into a valid hysteresis band.
    assert PressureController.parse_rungs("0.5:0.8") == [(0.5, 0.5)]
    assert PressureController.parse_rungs("nope") == [
        (0.60, 0.40), (0.80, 0.60), (0.95, 0.75)]


# ------------------------------------------------------------ token bucket


def test_token_bucket_interactive_charge_and_refill():
    adm = AdmissionController.from_spec("alice=2,*=0", burst_s=1.0)
    # Burst = rate x burst_s = 2 tokens from idle.
    assert adm.try_charge("alice")
    assert adm.try_charge("alice")
    assert not adm.try_charge("alice")  # dry
    # Honest Retry-After: ~1 token / 2 per s = 0.5 s, clamped >= 0.1.
    ra = adm.retry_after("alice")
    assert 0.1 <= ra <= 1.0
    # Unlimited tenants always admit.
    for _ in range(50):
        assert adm.try_charge("bob")
    time.sleep(0.6)  # ~1.2 tokens refilled
    assert adm.try_charge("alice")


def test_token_bucket_bulk_peek_charge_takes_debt():
    adm = AdmissionController.from_spec("job=10", burst_s=1.0)  # burst 10
    assert adm.peek("job", 8)
    # An oversize batch peeks against burst depth (would otherwise never
    # be admitted) and its charge takes token DEBT at dispatch.
    assert adm.peek("job", 64)
    adm.charge("job", 64)
    assert adm.stats()["tenants"]["job"]["tokens"] < -50
    assert not adm.peek("job", 1)  # debt repays at the quota rate
    assert adm.retry_after("job", 1) > 1.0


def test_tenant_cardinality_cap_collapses_to_other():
    adm = AdmissionController.from_spec("*=5", burst_s=1.0, max_tenants=2)
    adm.count_admit("t0", "interactive")
    adm.count_admit("t1", "interactive")
    for i in range(2, 8):
        adm.count_admit(f"t{i}", "interactive")
    st = adm.stats()
    assert set(st["tenants"]) == {"t0", "t1", OTHER_TENANT}
    assert st["tenants"][OTHER_TENANT]["admitted"] == 6
    assert st["classes"]["interactive"]["admitted"] == 8


def test_shed_accounting_by_tenant_class_reason():
    adm = AdmissionController.from_spec("")
    adm.count_shed("alice", "interactive", "quota")
    adm.count_shed("alice", "interactive", "quota")
    adm.count_shed("bob", "batch", "deadline")
    st = adm.stats()
    assert st["tenants"]["alice"]["shed"] == {"quota": 2}
    assert st["classes"]["batch"]["shed"] == {"deadline": 1}
    assert st["shed_by_reason"] == {"quota": 2, "deadline": 1}


# -------------------------------------------------------- pressure ladder


def test_pressure_ladder_walks_one_rung_per_dwell():
    pc = PressureController(
        rungs=[(0.6, 0.4), (0.8, 0.6), (0.95, 0.75)], dwell_s=1.0)
    # _changed_at is seeded with the real clock at construction; anchor
    # the injected timeline there.
    t = time.monotonic()
    # A saturating spike cannot teleport to reject: one rung per dwell.
    assert pc.observe_pressure(1.0, now=t) == 0  # inside the first dwell
    assert pc.observe_pressure(1.0, now=t + 1.0) == 1
    assert pc.observe_pressure(1.0, now=t + 1.5) == 1  # dwell holds it
    assert pc.observe_pressure(1.0, now=t + 2.0) == 2
    assert pc.observe_pressure(1.0, now=t + 3.0) == 3
    assert pc.observe_pressure(1.0, now=t + 9.0) == 3  # top rung pins
    # Hysteresis: frac between exit(0.75) and enter thresholds holds.
    assert pc.observe_pressure(0.8, now=t + 10.0) == 3
    # Recovery walks DOWN one rung per dwell too.
    assert pc.observe_pressure(0.1, now=t + 11.0) == 2
    assert pc.observe_pressure(0.1, now=t + 12.0) == 1
    assert pc.observe_pressure(0.1, now=t + 13.0) == 0
    st = pc.stats()
    assert st["level"] == 0 and st["action"] == "normal"
    assert st["transitions_total"] == 6
    assert st["entered_total"] == {"1": 1, "2": 1, "3": 1}


# ------------------------------------------------- batcher deadline sheds


def test_lease_deadline_shed_under_backlog_is_fast_and_counted():
    """A request whose deadline the expected wait cannot meet sheds at
    lease time — before decode or device work — and only under real
    backlog (an idle server never sheds on a stale estimate)."""
    eng = WedgeEngine()
    b = Batcher(eng, max_batch=1, max_delay_ms=1, pipeline_depth=1,
                max_queue=8)
    b.start()
    futures = []
    try:
        # Idle server: a meetable deadline is NOT shed at admission (zero
        # backlog means the estimate is all cold-start EMA noise).
        futures.append(b.submit(_canvas(0), (8, 8),
                                deadline=time.monotonic() + 30.0))
        time.sleep(0.2)  # batch 1 in flight, wedged at the fetch
        assert b.builder_stats()["deadline_sheds_total"] == 0
        futures.append(b.submit(_canvas(1), (8, 8)))
        time.sleep(0.2)  # batch 2 sealed, held at depth 1 -> backlog 1
        assert b.queue_depth >= 1

        t0 = time.monotonic()
        with pytest.raises(DeadlineExceeded) as ei:
            b.submit(_canvas(2), (8, 8), deadline=time.monotonic() - 1.0)
        assert time.monotonic() - t0 < 0.1  # shed, not queued
        assert ei.value.retry_after_s > 0
        assert b.builder_stats()["deadline_sheds_total"] == 1
    finally:
        eng.release.set()
        for f in futures:
            f.result(timeout=10)
        b.stop()
    assert eng.images == 2  # the shed request never reached the device


def test_seal_shed_flips_dead_rows_to_holes_without_leaks():
    """A committed row whose deadline passes while its batch waits at
    pipeline depth becomes a hole at seal: the future fails with
    DeadlineExceeded, the batch never ships the dead row, and no slot
    or depth accounting leaks."""
    eng = WedgeEngine()
    b = Batcher(eng, max_batch=1, max_delay_ms=1, pipeline_depth=1,
                max_queue=8)
    b.start()
    try:
        f_live = b.submit(_canvas(0), (8, 8))
        time.sleep(0.2)  # in flight, wedged
        f_dead = b.submit(_canvas(1), (8, 8),
                          deadline=time.monotonic() + 0.25)
        time.sleep(0.45)  # its deadline passes while held at depth
        eng.release.set()  # unwedge: the sealer re-checks at dispatch

        with pytest.raises(DeadlineExceeded, match="waited for dispatch"):
            f_dead.result(timeout=10)
        assert f_live.result(timeout=10) is not None

        deadline = time.monotonic() + 10
        while time.monotonic() < deadline:
            st = b.builder_stats()
            if st["inflight_batches"] == 0 and b.queue_depth == 0:
                break
            time.sleep(0.02)
        st = b.builder_stats()
        assert st["deadline_seal_sheds_total"] == 1
        assert st["holes_total"] >= 1
        assert st["inflight_batches"] == 0 and st["leased_slots"] == 0
    finally:
        eng.release.set()
        b.stop()
    assert eng.images == 1  # only the live row took device time


# -------------------------------------------------- quota before the valve


def test_bulk_quota_gates_before_starvation_valve():
    """Satellite regression: a quota-exhausted tenant's bulk batch must
    NOT ride the anti-starvation valve past its budget — the quota check
    runs first, holds are counted separately, and no starvation credit
    accrues while quota (not interactive pressure) is the blocker."""
    adm = AdmissionController.from_spec("job=10", burst_s=1.0)
    adm.charge("job", 100)  # deep token debt: ~9 s to repay
    eng = FastEngine()
    b = Batcher(eng, max_batch=2, max_delay_ms=1, pipeline_depth=2,
                bulk_max_batch=2, bulk_starvation_s=0.1, admission=adm)
    b.start()
    futures = []
    try:
        for i in range(2):  # full bulk builder -> closes -> gated
            futures.append(b.submit(_canvas(i), (8, 8), bulk=True,
                                    tenant="job"))
        time.sleep(0.5)  # 5 starvation windows pass
        st = b.builder_stats()["bulk"]
        assert eng.dispatches == 0, "quota-gated batch must not dispatch"
        assert st["quota_holds_total"] >= 1
        assert st["starvation_dispatches_total"] == 0
    finally:
        # Drain lifts the gate so stop() can flush the held batch.
        b.stop()
    for f in futures:
        f.result(timeout=10)
    assert eng.images == 2


# ------------------------------------------------------------- HTTP layer


def test_http_quota_429_with_reason_retry_after_and_counters():
    eng = FastEngine()
    b = Batcher(eng, max_batch=4, max_delay_ms=1)
    b.start()
    app = App(eng, b, _cfg(tenant_quota="alice=1", tenant_burst_s=1.0))
    try:
        status, _, _ = _post(app, body=b"\x01" * 16,
                             headers={"X-Tenant": "alice"})
        assert status.startswith("200")
        status, headers, body = _post(app, body=b"\x02" * 16,
                                      headers={"X-Tenant": "alice"})
        assert status.startswith("429")
        doc = json.loads(body)
        assert doc["reason"] == "quota" and doc["retry_after_s"] > 0
        assert int(headers["Retry-After"]) >= 1
        assert "X-Trace-Id" in headers
        # Unlimited tenants are untouched by alice's dry bucket.
        status, _, _ = _post(app, body=b"\x03" * 16,
                             headers={"X-Tenant": "bob"})
        assert status.startswith("200")

        adm = app._stats()["overload"]["admission"]
        assert adm["tenants"]["alice"]["admitted"] == 1
        assert adm["tenants"]["alice"]["shed"] == {"quota": 1}
        assert adm["tenants"]["bob"]["admitted"] == 1
        assert adm["shed_by_reason"]["quota"] == 1
        m = app._metrics()
        assert "tpu_serve_tenant_shed_total" in m and 'tenant="alice"' in m
        assert 'reason="quota"' in m
        assert "tpu_serve_quota_sheds_total 1" in m
    finally:
        b.stop()


def test_http_deadline_504_answers_fast_with_reason():
    """A wedged device + an explicit client deadline: the request is
    answered 504 at its deadline (reason "deadline", Retry-After set) —
    not held to the server-wide request timeout."""
    eng = WedgeEngine()
    b = Batcher(eng, max_batch=1, max_delay_ms=1, pipeline_depth=1)
    b.start()
    app = App(eng, b, _cfg())
    try:
        t0 = time.monotonic()
        status, headers, body = _post(app, qs="deadline_ms=250",
                                      headers={"X-Tenant": "carol"})
        elapsed = time.monotonic() - t0
        assert status.startswith("504")
        assert elapsed < 5.0, f"504 took {elapsed:.1f}s, not the deadline"
        doc = json.loads(body)
        assert doc["reason"] == "deadline"
        assert int(headers["Retry-After"]) >= 1
        adm = app._stats()["overload"]["admission"]
        assert adm["tenants"]["carol"]["shed"] == {"deadline": 1}
    finally:
        eng.release.set()
        b.stop()


def test_http_garbage_deadline_and_weightless_defaults():
    eng = FastEngine()
    b = Batcher(eng, max_batch=4, max_delay_ms=1)
    b.start()
    app = App(eng, b, _cfg())
    try:
        status, _, _ = _post(app, qs="deadline_ms=banana")
        assert status.startswith("400")
        # Naming an SLO class opts into its default deadline; a generous
        # class on a healthy server still answers 200.
        status, _, _ = _post(app, body=b"\x05" * 16, qs="slo=batch")
        assert status.startswith("200")
        adm = app._stats()["overload"]["admission"]
        assert adm["classes"]["batch"]["admitted"] == 1
    finally:
        b.stop()


def test_rung3_sheds_cache_misses_serves_hits():
    """Top ladder rung: cache-MISS work sheds 503/"degraded" while hits
    (the cheap work that keeps goodput up) still serve — and recovery
    is impossible with these rungs, so the level pins at 3."""
    eng = FastEngine()
    b = Batcher(eng, max_batch=4, max_delay_ms=1)
    b.start()
    # enter=0 always escalates, exit=-1 never recovers; dwell 0 lets
    # each request's own observation step one rung.
    app = App(eng, b, _cfg(cache_bytes=1 << 20,
                           pressure_rungs="0:-1,0:-1,0:-1",
                           pressure_dwell_s=0.0))
    try:
        body_a = b"\x11" * 16
        # Request 1 (level 0->1): miss, serves, warms the cache.
        status, _, _ = _post(app, body=body_a)
        assert status.startswith("200")
        # Request 2 (->2): rung 2 narrows the bucket set, which is part of
        # the key (the device may see other pixels for the same bytes), so
        # the rung's first answer for an upload is a miss of its own.
        status, headers, _ = _post(app, body=body_a)
        assert status.startswith("200") and headers["X-Cache"] == "miss"
        # Request 3 (->3): a hit on rung 2's entry — rung 3 serves hits.
        status, headers, _ = _post(app, body=body_a)
        assert status.startswith("200") and headers["X-Cache"] == "hit"
        # Request 4 at rung 3: a MISS is shed before decode/device time.
        status, headers, body = _post(app, body=b"\x22" * 16)
        assert status.startswith("503")
        doc = json.loads(body)
        assert doc["reason"] == "degraded"
        assert int(headers["Retry-After"]) >= 1

        pr = app._stats()["overload"]["pressure"]
        assert pr["level"] == 3 and pr["action"] == "reject_miss"
        assert pr["transitions_total"] == 3
        m = app._metrics()
        assert "tpu_serve_pressure_level 3" in m
        assert "tpu_serve_pressure_transitions_total 3" in m
        assert eng.images == 2  # two misses computed; shed miss never ran
    finally:
        b.stop()


# --------------------------------------------------------- SIGTERM drain


def test_sigterm_drains_inflight_interactive_never_hangs():
    """Satellite: SIGTERM with interactive requests in flight — every
    client gets a real answer (200 drained or 503 shed), none hang, and
    shutdown completes within the grace window."""
    import http.client

    eng = WedgeEngine()
    b = Batcher(eng, max_batch=1, max_delay_ms=1, pipeline_depth=1,
                max_queue=4)
    b.start()
    app = App(eng, b, _cfg(drain_grace_s=5.0))
    srv = make_http_server(app, "127.0.0.1", 0)
    threading.Thread(target=srv.serve_forever, daemon=True).start()
    port = srv.server_address[1]
    statuses = {}

    def req(slot):
        conn = http.client.HTTPConnection("127.0.0.1", port, timeout=30)
        try:
            conn.request("POST", "/predict", body=bytes([slot]) * 16,
                         headers={"Content-Type":
                                  "application/octet-stream"})
            statuses[slot] = conn.getresponse().status
        except Exception as e:  # a dropped connection is a hang-class bug
            statuses[slot] = f"error: {e}"
        finally:
            conn.close()

    threads = [threading.Thread(target=req, args=(i,)) for i in range(3)]
    for t in threads:
        t.start()
    time.sleep(0.4)  # requests in flight, device wedged

    # The wedge clears mid-shutdown — the drain must pick that up.
    threading.Timer(0.5, eng.release.set).start()
    t0 = time.monotonic()
    shutdown_gracefully(srv, b, grace_s=5.0)
    assert time.monotonic() - t0 < 10.0

    for t in threads:
        t.join(timeout=10)
    assert not any(t.is_alive() for t in threads), "client hung at SIGTERM"
    assert set(statuses) == {0, 1, 2}
    assert all(s in (200, 503) for s in statuses.values()), statuses
    assert 200 in statuses.values()  # the drain finished in-flight work
