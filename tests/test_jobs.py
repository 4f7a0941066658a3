"""Bulk offline inference jobs (serving/jobs.py, ISSUE 10): lifecycle
transitions, checkpoint/resume across a simulated restart, cancel
mid-run, result-stream offset resume + long-poll, hot-swap-under-job with
zero lost/duplicated images, cache-dedup accounting, graceful-shutdown
checkpointing, and the batcher's strict-priority bulk gate.

All on mock engines (no jax): the job manager is engine-agnostic by the
same seams the registry has; the real-engine bulk path (native decode
into 256-row slabs) is exercised by ``python bench.py bulk``.
"""

import http.client
import json
import tempfile
import threading
import time
from pathlib import Path

import numpy as np
import pytest

from tensorflow_web_deploy_tpu.serving.batcher import Batcher
from tensorflow_web_deploy_tpu.serving.http import (
    App, make_http_server, shutdown_gracefully,
)
from tensorflow_web_deploy_tpu.serving.jobs import (
    CANCELLED, DONE, JobManager, PAUSED, QUEUED, RUNNING, UnknownJob,
)
from tensorflow_web_deploy_tpu.serving.registry import ModelRegistry
from tensorflow_web_deploy_tpu.serving.respcache import ResponseCache
from tensorflow_web_deploy_tpu.utils.config import ModelConfig, ServerConfig


class _Mesh:
    devices = np.zeros(1)


class MockEngine:
    """Classify-shaped engine whose answers identify the engine instance
    (score == ``self.score``) and whose ``prepare_bytes`` derives the
    canvas from the upload bytes — distinct images get distinct content
    digests. ``fetch_gate`` (optional Event) holds every fetch open: the
    lever for deterministic mid-chunk interruption."""

    batch_buckets = (8,)
    max_batch = 8
    mesh = _Mesh()

    def __init__(self, score=0.5, fetch_gate=None, fetch_sem=None):
        self.score = score
        self.fetch_gate = fetch_gate
        # Counting gate: each permit admits exactly ONE batch fetch — the
        # deterministic way to stop a job between chunk N and chunk N+1
        # (one bulk chunk = one batch = one fetch at jobs_batch <= max_batch).
        self.fetch_sem = fetch_sem
        self.dispatches = 0
        self.images = 0

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
        self.images += len(canvases)
        return len(canvases)

    def fetch_outputs(self, handle):
        if self.fetch_gate is not None:
            assert self.fetch_gate.wait(timeout=30), "fetch gate never opened"
        if self.fetch_sem is not None:
            assert self.fetch_sem.acquire(timeout=30), "no fetch permit"
        n = handle
        scores = np.full((n, 5), self.score, np.float32)
        idx = np.tile(np.arange(5, dtype=np.int32), (n, 1))
        return scores, idx


def _mc(name="m1"):
    return ModelConfig(name=name, source="native", task="classify")


def _cfg(jobs_dir, cache_bytes=0, jobs_batch=4, jobs_max_inflight=1,
         name="m1"):
    return ServerConfig(model=_mc(name), max_batch=8, max_delay_ms=1.0,
                        request_timeout_s=10.0, drain_grace_s=3.0,
                        cache_bytes=cache_bytes, jobs_dir=jobs_dir,
                        jobs_batch=jobs_batch,
                        jobs_max_inflight=jobs_max_inflight)


def _image_dir(tmp_path, n, start=0):
    d = tmp_path / "corpus"
    d.mkdir(exist_ok=True)
    for i in range(start, start + n):
        (d / f"{i:03d}.jpg").write_bytes(bytes([(i % 250) + 1]) * 24)
    return str(d)


def _registry(cfg, fetch_gate=None, fetch_sem=None):
    counter = {"n": 0}
    engines = []

    def factory(mc):
        counter["n"] += 1
        e = MockEngine(score=round(0.1 * counter["n"], 3),
                       fetch_gate=fetch_gate, fetch_sem=fetch_sem)
        engines.append(e)
        return e

    r = ModelRegistry(cfg, engine_factory=factory, spec_resolver=_mc)
    r.load("m1", wait=True)
    return r, engines


def _wait_state(jm, job_id, states, timeout=20.0):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        doc = jm.get_job(job_id)
        if doc["state"] in states:
            return doc
        time.sleep(0.02)
    raise AssertionError(
        f"job never reached {states}: {jm.get_job(job_id)}")


def _indices(jm, job_id):
    lines, _off, _st, _tot = jm.read_results(job_id, 0, 100_000)
    return [json.loads(l)["i"] for l in lines]


# --------------------------------------------------------------- lifecycle


def test_lifecycle_done_with_history_and_ordered_results(tmp_path):
    cfg = _cfg(str(tmp_path / "jobs"))
    reg, engines = _registry(cfg)
    jm = JobManager(reg, ResponseCache(0), cfg)
    try:
        job = jm.submit_dir(_image_dir(tmp_path, 10), "m1", None)
        assert job.total == 10
        doc = _wait_state(jm, job.id, (DONE,))
        assert doc["completed"] == 10 and doc["errors"] == 0
        assert doc["chunks_done"] == 3  # 4 + 4 + 2 at jobs_batch=4
        assert doc["versions"] == ["m1@1"]
        states = [h["state"] for h in doc["history"]]
        assert states == [QUEUED, RUNNING, DONE]
        idx = _indices(jm, job.id)
        assert idx == list(range(10)), "results spool in manifest order"
        # Checkpoint on disk matches the terminal state.
        cp = json.loads(
            (Path(cfg.jobs_dir) / job.id / "checkpoint.json").read_text())
        assert cp["state"] == DONE and cp["completed"] == 10
        assert engines[0].images == 10  # every image computed exactly once
    finally:
        jm.stop(grace_s=5)
        reg.stop()


def test_oversize_manifest_refused_not_truncated(tmp_path):
    """A manifest past jobs_max_items must 400 at submit — a silent
    truncation would report DONE with images never processed."""
    cfg = _cfg(str(tmp_path / "jobs"))
    cfg.jobs_max_items = 5
    reg, _engines = _registry(cfg)
    jm = JobManager(reg, ResponseCache(0), cfg)
    try:
        src = _image_dir(tmp_path, 8)
        with pytest.raises(ValueError, match="jobs_max_items"):
            jm.submit_dir(src, "m1", None)
        with pytest.raises(ValueError, match="jobs_max_items"):
            jm.submit_upload([(f"i{i}.jpg", b"\x01" * 8) for i in range(6)],
                             "m1", None)
        # At the cap is fine.
        job = jm.submit_dir(src, "m1", None, glob="00[0-4].jpg")
        assert job.total == 5
        _wait_state(jm, job.id, (DONE,))
    finally:
        jm.stop(grace_s=5)
        reg.stop()


def test_results_offset_resume_and_longpoll(tmp_path):
    cfg = _cfg(str(tmp_path / "jobs"))
    reg, _ = _registry(cfg)
    jm = JobManager(reg, ResponseCache(0), cfg)
    try:
        job = jm.submit_dir(_image_dir(tmp_path, 9), "m1", None)
        _wait_state(jm, job.id, (DONE,))
        l1, off1, _, total = jm.read_results(job.id, 0, 4)
        assert len(l1) == 4 and off1 == 4 and total == 9
        l2, off2, state, _ = jm.read_results(job.id, off1, 100)
        assert len(l2) == 5 and off2 == 9 and state == DONE
        got = [json.loads(l)["i"] for l in l1 + l2]
        assert got == list(range(9)), "offset resume must not skip or repeat"
        # Long-poll past the end of a terminal job returns immediately.
        t0 = time.monotonic()
        l3, off3, state, _ = jm.read_results(job.id, 9, 100, wait_s=5.0)
        assert l3 == [] and off3 == 9 and state == DONE
        assert time.monotonic() - t0 < 2.0
    finally:
        jm.stop(grace_s=5)
        reg.stop()


def test_cancel_mid_run_keeps_completed_chunks(tmp_path):
    sem = threading.Semaphore(0)
    cfg = _cfg(str(tmp_path / "jobs"))
    reg, _ = _registry(cfg, fetch_sem=sem)
    jm = JobManager(reg, ResponseCache(0), cfg)
    try:
        job = jm.submit_dir(_image_dir(tmp_path, 12), "m1", None)
        # Admit exactly chunk 1's fetch; chunk 2 blocks at the device.
        sem.release()
        deadline = time.monotonic() + 10
        while jm.get_job(job.id)["completed"] < 4:
            assert time.monotonic() < deadline
            time.sleep(0.01)
        jm.cancel_job(job.id)
        for _ in range(8):
            sem.release()  # the in-flight chunk resolves, then cancel lands
        doc = _wait_state(jm, job.id, (CANCELLED,))
        assert 0 < doc["completed"] < 12, "completed chunks survive a cancel"
        idx = _indices(jm, job.id)
        assert idx == list(range(doc["result_lines"]))
        # A cancelled job is terminal: cancel again is a no-op, results stay.
        assert jm.cancel_job(job.id)["state"] == CANCELLED
    finally:
        for _ in range(16):
            sem.release()
        jm.stop(grace_s=5)
        reg.stop()


# ------------------------------------------------------- checkpoint/resume


def test_checkpoint_resume_after_simulated_restart(tmp_path):
    """Interrupt a running job (manager stop with the device stalled =
    the SIGTERM shape), then construct a FRESH manager over the same
    jobs_dir — the restart. The job must resume from its chunk checkpoint
    and finish with zero lost and zero duplicated images."""
    sem = threading.Semaphore(0)
    cfg = _cfg(str(tmp_path / "jobs"))
    reg, engines = _registry(cfg, fetch_sem=sem)
    jm = JobManager(reg, ResponseCache(0), cfg)
    job = jm.submit_dir(_image_dir(tmp_path, 14), "m1", None)
    # Admit exactly chunk 1's fetch; chunk 2 stalls at the device.
    sem.release()
    deadline = time.monotonic() + 10
    while jm.get_job(job.id)["completed"] < 4:
        assert time.monotonic() < deadline
        time.sleep(0.01)
    # Stop with a short grace: the runner is blocked on the stalled chunk,
    # so the join times out — exactly a hard SIGTERM under load.
    jm.stop(grace_s=0.2)
    for _ in range(8):
        sem.release()  # the chunk resolves; the runner exits at the boundary
    runner = jm._runner
    if runner is not None:
        runner.join(timeout=20)  # the "process" must be dead pre-restart
        assert not runner.is_alive()
    persisted = json.loads(
        (Path(cfg.jobs_dir) / job.id / "checkpoint.json").read_text())
    assert persisted["state"] == RUNNING, "interrupted jobs persist RUNNING"
    assert 4 <= persisted["completed"] < 14

    for _ in range(32):
        sem.release()  # the restarted run fetches freely
    jm2 = JobManager(reg, ResponseCache(0), cfg)  # the restart
    try:
        doc = jm2.get_job(job.id)
        assert doc["resumed"] is True
        doc = _wait_state(jm2, job.id, (DONE,))
        assert doc["completed"] == 14
        idx = _indices(jm2, job.id)
        assert sorted(idx) == list(range(14)), "zero lost"
        assert len(set(idx)) == len(idx), "zero duplicated"
        assert idx == sorted(idx), "manifest order preserved across resume"
    finally:
        jm2.stop(grace_s=5)
        reg.stop()


def test_recovery_truncates_results_past_checkpoint(tmp_path):
    """A crash between the results append and the checkpoint update leaves
    over-appended lines; recovery must truncate them so the replayed
    chunk cannot duplicate."""
    cfg = _cfg(str(tmp_path / "jobs"))
    reg, _ = _registry(cfg)
    jm = JobManager(reg, ResponseCache(0), cfg)
    job = jm.submit_dir(_image_dir(tmp_path, 8), "m1", None)
    _wait_state(jm, job.id, (DONE,))
    jm.stop(grace_s=5)
    jdir = Path(cfg.jobs_dir) / job.id
    # Rewind the checkpoint to chunk 1 and append garbage past it — the
    # worst-case torn write.
    cp = json.loads((jdir / "checkpoint.json").read_text())
    results = (jdir / "results.jsonl").read_bytes()
    lines = results.splitlines(keepends=True)
    cp.update(state=RUNNING, completed=4, result_lines=4,
              result_bytes=sum(len(l) for l in lines[:4]), chunks_done=1)
    (jdir / "checkpoint.json").write_text(json.dumps(cp))
    with open(jdir / "results.jsonl", "ab") as f:
        f.write(b'{"i": 999, "torn": true}\n')

    jm2 = JobManager(reg, ResponseCache(0), cfg)
    try:
        doc = _wait_state(jm2, job.id, (DONE,))
        assert doc["completed"] == 8
        idx = _indices(jm2, job.id)
        assert idx == list(range(8)), f"torn tail must not survive: {idx}"
    finally:
        jm2.stop(grace_s=5)
        reg.stop()


# ------------------------------------------------------- hot-swap-under-job


def test_hot_swap_under_job_pauses_reversions_zero_lost(tmp_path):
    sem = threading.Semaphore(0)
    cfg = _cfg(str(tmp_path / "jobs"))
    cfg.drain_grace_s = 15.0  # v1 must outlive the PAUSED observation below
    reg, engines = _registry(cfg, fetch_sem=sem)
    jm = JobManager(reg, ResponseCache(0), cfg)
    try:
        job = jm.submit_dir(_image_dir(tmp_path, 20), "m1", None)
        # Chunk 1 lands; chunk 2 blocks at v1's device fetch — the job is
        # mid-flight when the swap arrives.
        sem.release()
        deadline = time.monotonic() + 10
        while jm.get_job(job.id)["completed"] < 4:
            assert time.monotonic() < deadline
            time.sleep(0.01)
        # Swap in the background: v2 warms + SERVES, then v1 DRAINs — the
        # retire listener fires at the DRAINING flip and must PAUSE the
        # job while its chunk is still in flight on v1.
        swapper = threading.Thread(
            target=lambda: reg.swap("m1", wait=True, timeout=60), daemon=True)
        swapper.start()
        doc = _wait_state(jm, job.id, (PAUSED,), timeout=10)
        assert doc["state"] == PAUSED
        # Release the world: the v1 chunk resolves (or retries on v2), the
        # job resumes on the successor and finishes.
        for _ in range(64):
            sem.release()
        swapper.join(timeout=60)
        old = reg._models["m1"][1]
        reg.wait_for(old, ("UNLOADED",), timeout=30)
        doc = _wait_state(jm, job.id, (DONE,))
        states = [h["state"] for h in doc["history"]]
        assert PAUSED in states, f"drain must pause the job: {states}"
        assert states[-1] == DONE
        assert doc["versions"] == ["m1@1", "m1@2"], (
            "remaining work re-versions onto the successor"
        )
        idx = _indices(jm, job.id)
        assert sorted(idx) == list(range(20)), "zero lost"
        assert len(set(idx)) == 20, "zero duplicated"
        # Both engines actually computed work (the swap happened mid-job).
        # Dispatch counts may exceed the manifest if a drain-killed batch
        # retried on v2 — the RESULT uniqueness above is the no-dup proof.
        assert engines[0].images > 0 and engines[1].images > 0
        assert engines[0].images + engines[1].images >= 20
    finally:
        for _ in range(64):
            sem.release()
        jm.stop(grace_s=5)
        reg.stop()


# -------------------------------------------------------------- cache dedup


def test_cache_dedup_accounting_and_interactive_prewarm(tmp_path):
    """A duplicate-heavy manifest dedups through the response cache (bulk
    counters, not interactive ones), and the job's inserts pre-warm the
    cache for the interactive tier."""
    d = tmp_path / "corpus"
    d.mkdir()
    blobs = [b"\x01" * 30, b"\x02" * 30, b"\x03" * 30]
    for i in range(12):  # 12 items, 3 distinct contents
        (d / f"{i:03d}.jpg").write_bytes(blobs[i % 3])
    cfg = _cfg(str(tmp_path / "jobs"), cache_bytes=1 << 20)
    reg, engines = _registry(cfg)
    cache = ResponseCache(1 << 20)
    jm = JobManager(reg, cache, cfg)
    try:
        job = jm.submit_dir(str(d), "m1", None)
        doc = _wait_state(jm, job.id, (DONE,))
        assert doc["completed"] == 12 and doc["errors"] == 0
        assert doc["cached"] == 9, (
            "9 of 12 images are duplicates and must dedup (hit or coalesce)"
        )
        s = cache.stats()
        assert s["bulk"]["misses_total"] == 3
        assert s["bulk"]["hits_total"] + s["bulk"]["coalesced_total"] == 9
        # Bulk accounting never leaks into the interactive counters.
        assert s["hits_total"] == 0 and s["misses_total"] == 0
        # The job populated the cache: an interactive-tier lookup for the
        # same content is a warm hit.
        from tensorflow_web_deploy_tpu.serving.respcache import (
            make_key, upload_digest,
        )
        mv = reg.acquire("m1")
        try:
            # The mock engine has no leases, so no wire rides in the key.
            digest = upload_digest(blobs[0], cfg.canvas_buckets, None)
            key = make_key(mv.name, mv.version, digest, mv.model_cfg.topk)
            kind, _ = cache.begin(key, mv.name)
            assert kind == "hit", "job results must pre-warm the interactive tier"
        finally:
            reg.release(mv)
        assert cache.stats()["hits_total"] == 1
    finally:
        jm.stop(grace_s=5)
        reg.stop()


# ------------------------------------------------------------- HTTP surface


@pytest.fixture()
def jobs_server(tmp_path):
    cfg = _cfg(str(tmp_path / "jobs"), cache_bytes=1 << 20)
    reg, engines = _registry(cfg)
    app = App.from_registry(reg, cfg)
    srv = make_http_server(app, "127.0.0.1", 0, pool_size=6)
    threading.Thread(target=srv.serve_forever, daemon=True).start()
    yield srv.server_address[1], reg, app, engines, tmp_path
    shutdown_gracefully(srv, reg, grace_s=3.0)


def _req(port, method, path, body=None, ctype="application/json", timeout=20):
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=timeout)
    try:
        headers = {"Content-Type": ctype} if body is not None else {}
        conn.request(method, path, body=body, headers=headers)
        resp = conn.getresponse()
        data = resp.read()
        return resp.status, data, dict(
            (k.lower(), v) for k, v in resp.getheaders())
    finally:
        conn.close()


def _multipart(images):
    boundary = "jobtestboundary"
    parts = b"".join(
        (f'--{boundary}\r\nContent-Disposition: form-data; name="f{i}"; '
         f'filename="im{i}.jpg"\r\n\r\n').encode() + img + b"\r\n"
        for i, img in enumerate(images)
    )
    return (parts + f"--{boundary}--\r\n".encode(),
            f"multipart/form-data; boundary={boundary}")


def test_http_submit_poll_results_stats_metrics(jobs_server):
    from tensorflow_web_deploy_tpu.utils.metrics import parse_prometheus_text

    port, reg, app, engines, _tmp = jobs_server
    body, ctype = _multipart([bytes([i + 1]) * 20 for i in range(10)])
    status, data, _ = _req(port, "POST", "/jobs?topk=3", body, ctype)
    assert status == 202, data
    doc = json.loads(data)
    jid = doc["id"]
    assert doc["state"] in (QUEUED, RUNNING) and doc["total"] == 10
    # Poll /jobs/{id} to terminal.
    deadline = time.monotonic() + 20
    while True:
        status, data, _ = _req(port, "GET", f"/jobs/{jid}")
        assert status == 200
        doc = json.loads(data)
        if doc["state"] in (DONE, "FAILED", CANCELLED):
            break
        assert time.monotonic() < deadline, doc
        time.sleep(0.05)
    assert doc["state"] == DONE and doc["completed"] == 10
    # Offset-resumable result stream with the header cursor.
    status, data, hdrs = _req(port, "GET", f"/jobs/{jid}/results?offset=6")
    assert status == 200 and hdrs["content-type"] == "application/x-ndjson"
    lines = data.decode().strip().split("\n")
    assert len(lines) == 4
    assert [json.loads(l)["i"] for l in lines] == [6, 7, 8, 9]
    assert hdrs["x-job-next-offset"] == "10"
    assert hdrs["x-job-state"] == DONE and hdrs["x-job-complete"] == "1"
    # topk=3 honored in the payload.
    assert len(json.loads(lines[0])["predictions"]) == 3
    # /jobs listing + /stats + /metrics blocks.
    status, data, _ = _req(port, "GET", "/jobs")
    assert status == 200 and any(
        j["id"] == jid for j in json.loads(data)["jobs"])
    status, data, _ = _req(port, "GET", "/stats")
    snap = json.loads(data)
    assert snap["jobs"]["enabled"] and snap["jobs"]["images_done_total"] == 10
    assert snap["config"]["jobs_batch"] == 4
    status, data, _ = _req(port, "GET", "/metrics")
    samples = parse_prometheus_text(data.decode())["samples"]
    assert samples[("tpu_serve_job_images_done_total", ())] == 10
    assert samples[("tpu_serve_jobs", (("state", "DONE"),))] >= 1
    assert samples[("tpu_serve_job_chunks_total", ())] >= 3


def test_http_submit_server_dir_and_cancel_route(jobs_server):
    port, reg, app, engines, tmp_path = jobs_server
    src = _image_dir(tmp_path, 6)
    body = json.dumps({"dir": src, "glob": "*.jpg"}).encode()
    status, data, _ = _req(port, "POST", "/jobs", body)
    assert status == 202, data
    jid = json.loads(data)["id"]
    status, data, _ = _req(port, "POST", f"/jobs/{jid}/cancel", b"")
    assert status == 200
    # Cancel races completion: either is terminal, nothing hangs.
    deadline = time.monotonic() + 20
    while True:
        doc = json.loads(_req(port, "GET", f"/jobs/{jid}")[1])
        if doc["state"] in (DONE, CANCELLED):
            break
        assert time.monotonic() < deadline
        time.sleep(0.05)


def test_http_validation_and_disabled(jobs_server, tmp_path):
    port, reg, app, engines, _tmp = jobs_server
    # Unknown model → 404 at submit, not a FAILED job later.
    body, ctype = _multipart([b"x" * 10])
    status, data, _ = _req(port, "POST", "/jobs?model=nosuch", body, ctype)
    assert status == 404, data
    # Version pins refused: jobs survive hot-swaps by design.
    status, data, _ = _req(port, "POST", "/jobs?model=m1%401", body, ctype)
    assert status == 400 and b"pinned" in data
    # Server-side dir that does not exist → 400.
    status, data, _ = _req(
        port, "POST", "/jobs", json.dumps({"dir": "/nonexistent-xyz"}).encode())
    assert status == 400
    # Neither multipart nor a dir body → 400.
    status, data, _ = _req(port, "POST", "/jobs", b"{}")
    assert status == 400
    # Garbage topk in the JSON body → 400 at submit, same as the
    # query-string gate — never a 202 that FAILs at the first chunk.
    status, data, _ = _req(
        port, "POST", "/jobs",
        json.dumps({"dir": str(tmp_path), "topk": "lots"}).encode())
    assert status == 400 and b"topk" in data
    # Unknown job id → 404.
    assert _req(port, "GET", "/jobs/j99999-abcdef")[0] == 404
    assert _req(port, "GET", "/jobs/j99999-abcdef/results")[0] == 404
    # Jobs disabled (no --jobs-dir) → 503 with the hint.
    cfg2 = ServerConfig(model=_mc("m2"), max_batch=8, cache_bytes=0)
    reg2 = ModelRegistry(cfg2, engine_factory=lambda mc: MockEngine(),
                         spec_resolver=lambda s: _mc("m2"))
    reg2.load("m2", wait=True)
    app2 = App.from_registry(reg2, cfg2)
    srv2 = make_http_server(app2, "127.0.0.1", 0, pool_size=2)
    threading.Thread(target=srv2.serve_forever, daemon=True).start()
    try:
        status, data, _ = _req(srv2.server_address[1], "POST", "/jobs",
                               body, ctype)
        assert status == 503 and b"--jobs-dir" in data
    finally:
        shutdown_gracefully(srv2, reg2, grace_s=3.0)


# ------------------------------------------------------- graceful shutdown


def test_graceful_shutdown_checkpoints_running_job(tmp_path):
    """The SIGTERM path: shutdown_gracefully auto-discovers the app's job
    manager and stops it FIRST — the runner checkpoints at its chunk
    boundary, and a restart resumes with zero lost/duplicated images.
    Before this existed, an in-flight bulk workload was silently lost."""
    gate = threading.Event()
    cfg = _cfg(str(tmp_path / "jobs"))
    reg, engines = _registry(cfg, fetch_gate=gate)
    app = App.from_registry(reg, cfg)
    srv = make_http_server(app, "127.0.0.1", 0, pool_size=4)
    threading.Thread(target=srv.serve_forever, daemon=True).start()
    port = srv.server_address[1]
    src = _image_dir(tmp_path, 12)
    status, data, _ = _req(port, "POST", "/jobs",
                           json.dumps({"dir": src}).encode())
    assert status == 202
    jid = json.loads(data)["id"]
    gate.set()
    deadline = time.monotonic() + 10
    while json.loads(_req(port, "GET", f"/jobs/{jid}")[1])["completed"] < 4:
        assert time.monotonic() < deadline
        time.sleep(0.01)
    # SIGTERM → KeyboardInterrupt → shutdown_gracefully (server.py main):
    # the manager stops first, the in-flight chunk resolves against the
    # still-live batcher, and its checkpoint lands before batchers drain.
    shutdown_gracefully(srv, reg, grace_s=10.0)
    runner = app.jobs._runner
    if runner is not None:
        runner.join(timeout=20)
    cp = json.loads(
        (Path(cfg.jobs_dir) / jid / "checkpoint.json").read_text())
    assert cp["state"] in (RUNNING, DONE)
    assert cp["completed"] >= 4, "progress at shutdown must be durable"
    assert cp["completed"] == cp["result_lines"]

    # Restart: fresh registry + manager over the same jobs_dir.
    reg2, _ = _registry(cfg)
    jm2 = JobManager(reg2, ResponseCache(0), cfg)
    try:
        doc = _wait_state(jm2, jid, (DONE,))
        assert doc["completed"] == 12
        idx = _indices(jm2, jid)
        assert sorted(idx) == list(range(12)) and len(set(idx)) == 12
    finally:
        jm2.stop(grace_s=5)
        reg2.stop()


# ------------------------------------------------------ bulk priority gate


def test_failed_stage_aborts_led_flight(tmp_path):
    """A batcher raising AFTER the cache flight is led (the hot-swap
    drain / SIGTERM race) must abort the flight: a leaked flight would
    wedge every interactive request coalescing onto that key until its
    own timeout."""
    from types import SimpleNamespace

    from tensorflow_web_deploy_tpu.serving.batcher import ShuttingDown
    from tensorflow_web_deploy_tpu.serving.staging import stage_image

    cache = ResponseCache(1 << 20)
    cfg = _cfg(str(tmp_path / "jobs"), cache_bytes=1 << 20)
    reg, _engines = _registry(cfg)
    jm = JobManager(reg, cache, cfg)
    try:
        class DownBatcher:
            supports_lease = False

            def submit(self, canvas, hw, bulk=False, **admit):
                raise ShuttingDown("draining under hot-swap")

        mv = SimpleNamespace(name="m1", version=1, model_cfg=_mc("m1"),
                             engine=MockEngine(), labels=["a", "b"])
        with pytest.raises(ShuttingDown):
            stage_image(b"\x01" * 16, batcher=DownBatcher(), mv=mv,
                        cache=cache, topk=3, buckets=cfg.canvas_buckets,
                        span=None, bulk=True)
        st = cache.stats()
        assert st["inflight"] == 0, "led flight must be aborted, not leaked"
        # The key is immediately re-leadable — a fresh attempt is not a
        # coalesced waiter on a dead computation.
        from tensorflow_web_deploy_tpu.serving.respcache import (
            make_key, upload_digest,
        )
        digest = upload_digest(b"\x01" * 16, cfg.canvas_buckets, None)
        kind, _obj = cache.begin(make_key("m1", 1, digest, 3), "m1",
                                 bulk=True)
        assert kind == "lead"
    finally:
        jm.stop(grace_s=3)
        reg.stop()


def test_bulk_gate_strict_priority_and_batch_size(tmp_path):
    """Batcher-level isolation contract: a sealed bulk batch dispatches
    only when the interactive pipeline has idle depth; while interactive
    batches hold the device, bulk work keeps assembling (bigger batches)
    instead of queueing in front of anyone."""
    gate = threading.Event()
    eng = MockEngine(fetch_gate=gate)
    # Starvation valve parked far out: THIS test pins the strict gate.
    b = Batcher(eng, max_batch=2, max_delay_ms=1.0, pipeline_depth=1,
                bulk_max_batch=8, bulk_inflight=1, bulk_starvation_s=30.0)
    b.start()
    try:
        canvas = np.zeros((8, 8, 3), np.uint8)
        # One interactive batch in flight, gate closed: it holds depth 1.
        it_fut = b.submit(canvas, (8, 8))
        deadline = time.monotonic() + 5
        while b.inflight_batches < 1:
            assert time.monotonic() < deadline
            time.sleep(0.005)
        # Bulk work arrives: a full bulk builder seals but must NOT
        # dispatch while the interactive pipeline is at depth.
        bulk_futs = [b.submit(canvas, (8, 8), bulk=True) for _ in range(8)]
        deadline = time.monotonic() + 3
        while b.builder_stats()["bulk"]["gate_holds_total"] == 0:
            assert time.monotonic() < deadline, b.builder_stats()
            time.sleep(0.005)
        bs = b.builder_stats()["bulk"]
        assert bs["inflight_batches"] == 0, "bulk must wait for idle depth"
        assert not it_fut.done()
        # Interactive completes → the gate opens → bulk dispatches as ONE
        # full batch (it grew while gated).
        gate.set()
        it_fut.result(timeout=10)
        for f in bulk_futs:
            f.result(timeout=10)
        bs = b.builder_stats()["bulk"]
        assert bs["batches_sealed_total"] == 1
        assert bs["images_sealed_total"] == 8
    finally:
        gate.set()
        b.stop()


def test_bulk_starvation_valve_admits_under_sustained_load(tmp_path):
    """Closed-loop interactive clients keep the pipeline non-idle forever;
    the anti-starvation valve must still admit one bulk batch per window
    — strict priority degrades bulk to slow, never to zero."""
    gate = threading.Event()  # held: the interactive batch never completes
    eng = MockEngine(fetch_gate=gate)
    b = Batcher(eng, max_batch=2, max_delay_ms=1.0, pipeline_depth=2,
                bulk_max_batch=8, bulk_inflight=1, bulk_starvation_s=0.3)
    b.start()
    try:
        canvas = np.zeros((8, 8, 3), np.uint8)
        it_fut = b.submit(canvas, (8, 8))
        deadline = time.monotonic() + 5
        while b.inflight_batches < 1:
            assert time.monotonic() < deadline
            time.sleep(0.005)
        bulk_futs = [b.submit(canvas, (8, 8), bulk=True) for _ in range(8)]
        # With the interactive batch pinned in flight the idle gate never
        # opens — the valve must fire within ~bulk_starvation_s.
        deadline = time.monotonic() + 5
        while b.builder_stats()["bulk"]["inflight_batches"] == 0:
            assert time.monotonic() < deadline, b.builder_stats()["bulk"]
            time.sleep(0.01)
        bs = b.builder_stats()["bulk"]
        assert bs["starvation_dispatches_total"] >= 1
        gate.set()
        it_fut.result(timeout=10)
        for f in bulk_futs:
            f.result(timeout=10)
    finally:
        gate.set()
        b.stop()


def test_bulk_valve_clock_resets_after_discarded_batch(tmp_path):
    """A gated bulk batch whose leases all abort into holes (cancel path)
    is discarded without dispatching — the starvation clock must reset
    with it, or the NEXT job's first batch inherits an instantly-open
    valve and jumps the interactive tier with zero actual gated time."""
    gate = threading.Event()
    eng = MockEngine(fetch_gate=gate)
    b = Batcher(eng, max_batch=2, max_delay_ms=1.0, pipeline_depth=1,
                bulk_max_batch=2, bulk_inflight=1, bulk_starvation_s=1.5)
    b.start()
    try:
        canvas = np.zeros((8, 8, 3), np.uint8)
        it_fut = b.submit(canvas, (8, 8))  # pins the gate closed
        deadline = time.monotonic() + 5
        while b.inflight_batches < 1:
            assert time.monotonic() < deadline
            time.sleep(0.005)
        # A real sealed bulk batch, gated: the clock starts.
        l1 = b.lease((8, 8, 3), bulk=True)
        l1.commit((8, 8), canvas=canvas)
        l2 = b.lease((8, 8, 3), bulk=True)
        l2.commit((8, 8), canvas=canvas)
        deadline = time.monotonic() + 3
        while b.builder_stats()["bulk"]["gate_holds_total"] == 0:
            assert time.monotonic() < deadline, b.builder_stats()["bulk"]
            time.sleep(0.005)
        # Cancel-style abort: both leases release into holes → the sealed
        # batch evaporates and is discarded, never dispatched.
        l1.release()
        l2.release()
        time.sleep(0.1)
        assert b.builder_stats()["bulk"]["inflight_batches"] == 0
        # A NEW job's first batch under the still-busy interactive tier:
        # a stale clock would valve it through instantly.
        futs = [b.submit(canvas, (8, 8), bulk=True) for _ in range(2)]
        t_probe = time.monotonic() + 0.5  # well under bulk_starvation_s
        while time.monotonic() < t_probe:
            bs = b.builder_stats()["bulk"]
            assert bs["starvation_dispatches_total"] == 0, \
                "valve fired with zero gated time (stale clock)"
            assert bs["inflight_batches"] == 0
            time.sleep(0.02)
        gate.set()
        it_fut.result(timeout=10)
        for f in futs:
            f.result(timeout=10)
    finally:
        gate.set()
        b.stop()


def test_bulk_backpressure_blocks_without_rejecting(tmp_path):
    """Bulk leasing never raises BacklogFull even on a bounded-queue
    batcher — the job runner blocks instead, and the interactive bound is
    untouched by bulk backlog."""
    gate = threading.Event()
    eng = MockEngine(fetch_gate=gate)
    b = Batcher(eng, max_batch=2, max_delay_ms=1.0, pipeline_depth=1,
                max_queue=4, bulk_max_batch=4, bulk_inflight=1)
    b.start()
    try:
        canvas = np.zeros((8, 8, 3), np.uint8)
        # Fill bulk far past its cap from a side thread: it must block
        # (not raise), and interactive leases must still be admitted.
        submitted = []
        done = threading.Event()

        def flood():
            for _ in range(20):
                submitted.append(b.submit(canvas, (8, 8), bulk=True))
            done.set()

        t = threading.Thread(target=flood, daemon=True)
        t.start()
        time.sleep(0.3)
        assert not done.is_set(), "bulk flood must hit the blocking cap"
        it_fut = b.submit(canvas, (8, 8))  # interactive unaffected
        gate.set()
        it_fut.result(timeout=10)
        assert done.wait(timeout=15), "bulk flood must drain once gated work flows"
        for f in submitted:
            f.result(timeout=15)
        assert b.builder_stats()["backlog_rejections_total"] == 0
    finally:
        gate.set()
        b.stop()


def test_result_rows_carry_trace_ids_joining_chunk_spans(tmp_path):
    """Satellite: every spooled result row carries a trace_id that joins
    against the chunk spans in the flight recorder (/debug/trace, access
    log) — and those spans are tagged class=bulk."""
    from tensorflow_web_deploy_tpu.utils.metrics import Observability

    cfg = _cfg(str(tmp_path / "jobs"))
    reg, _engines = _registry(cfg)
    obs = Observability()
    jm = JobManager(reg, ResponseCache(0), cfg, obs=obs)
    try:
        job = jm.submit_dir(_image_dir(tmp_path, 6), "m1", None)
        _wait_state(jm, job.id, (DONE,))
        lines = (Path(cfg.jobs_dir) / job.id / "results.jsonl").read_text()
        rows = [json.loads(ln) for ln in lines.splitlines()]
        assert len(rows) == 6
        assert all(r.get("trace_id") for r in rows)
        bulk_spans = [d for _t0, _t1, d in obs.flight.trace_records(None)
                      if d.get("class") == "bulk"]
        assert bulk_spans, "chunk spans must reach the recorder as bulk"
        span_ids = {d["trace_id"] for d in bulk_spans}
        # Every row's trace joins a recorded bulk chunk span; 6 images at
        # jobs_batch=4 = 2 chunks = 2 distinct trace ids.
        assert {r["trace_id"] for r in rows} <= span_ids
        assert len({r["trace_id"] for r in rows}) == 2
    finally:
        jm.stop(grace_s=5)
        reg.stop()
