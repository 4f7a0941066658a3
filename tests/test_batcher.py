"""Batcher unit tests (SURVEY.md §4): max-batch, ordering, error isolation."""

import queue
import threading
import time

import numpy as np
import pytest

from tensorflow_web_deploy_tpu.serving.batcher import Batcher


class FakeEngine:
    """Echoes (canvas tag + hw-sum) per row so results are attributable.
    Implements the engine's dispatch/fetch pair; work happens in fetch,
    mirroring the real engine's async device semantics."""

    def __init__(self, fail_on=None, delay_s=0.0):
        self.batches: list[int] = []
        self.fail_on = fail_on or set()
        self.delay_s = delay_s

    def dispatch_batch(self, canvases, hws):
        self.batches.append(len(canvases))
        return canvases, hws

    def fetch_outputs(self, handle):
        canvases, hws = handle
        if self.delay_s:
            time.sleep(self.delay_s)
        tags = canvases.reshape(len(canvases), -1)[:, 0].astype(np.float64)
        if any(int(t) in self.fail_on for t in tags):
            raise RuntimeError("poisoned batch")
        return (tags + hws.sum(axis=1),)

    def run_batch(self, canvases, hws):
        return self.fetch_outputs(self.dispatch_batch(canvases, hws))


def _canvas(tag, size=8):
    c = np.full((size, size, 3), tag, np.uint8)
    return c


def test_results_routed_to_correct_requests():
    eng = FakeEngine()
    b = Batcher(eng, max_batch=4, max_delay_ms=10)
    b.start()
    futures = [b.submit(_canvas(i), (i, i)) for i in range(10)]
    results = [f.result(timeout=5)[0] for f in futures]
    b.stop()
    assert results == [i + 2 * i for i in range(10)]


def test_batching_happens_under_load():
    eng = FakeEngine(delay_s=0.02)
    b = Batcher(eng, max_batch=8, max_delay_ms=20)
    b.start()
    futures = [b.submit(_canvas(i), (1, 1)) for i in range(16)]
    for f in futures:
        f.result(timeout=5)
    b.stop()
    # While the first batch is on-device, the rest queue up and batch.
    assert max(eng.batches) > 1
    assert sum(eng.batches) == 16


def test_max_batch_respected():
    eng = FakeEngine(delay_s=0.05)
    b = Batcher(eng, max_batch=4, max_delay_ms=50)
    b.start()
    futures = [b.submit(_canvas(i), (1, 1)) for i in range(12)]
    for f in futures:
        f.result(timeout=5)
    b.stop()
    assert max(eng.batches) <= 4


def test_mixed_canvas_sizes_grouped():
    eng = FakeEngine(delay_s=0.05)
    b = Batcher(eng, max_batch=16, max_delay_ms=30)
    b.start()
    # Warm the dispatcher with one request so the rest enqueue together.
    b.submit(_canvas(0, 8), (1, 1)).result(timeout=5)
    futures = [b.submit(_canvas(i, 8 if i % 2 else 16), (1, 1)) for i in range(8)]
    for f in futures:
        f.result(timeout=5)
    b.stop()
    assert sum(eng.batches) == 9  # no request lost across shape groups


def test_failed_batch_isolates_to_its_requests():
    eng = FakeEngine(fail_on={3})
    b = Batcher(eng, max_batch=1, max_delay_ms=1)  # one request per batch
    b.start()
    futures = [b.submit(_canvas(i), (1, 1)) for i in range(6)]
    ok, failed = 0, 0
    for i, f in enumerate(futures):
        try:
            f.result(timeout=5)
            ok += 1
        except RuntimeError:
            failed += 1
    b.stop()
    assert failed == 1 and ok == 5
    assert b.stats.snapshot()["errors_total"] == 1


def test_failed_requests_keep_their_latency():
    """Errored requests are often the slowest; their timing must land in
    the error-latency window instead of vanishing from every percentile."""
    eng = FakeEngine(fail_on={0}, delay_s=0.02)
    b = Batcher(eng, max_batch=1, max_delay_ms=1)
    b.start()
    f = b.submit(_canvas(0), (1, 1))
    with pytest.raises(RuntimeError):
        f.result(timeout=5)
    b.stop()
    snap = b.stats.snapshot()
    err = snap["error_latency_ms"]
    assert err["count"] == 1
    assert err["p50"] >= 20.0  # at least the fake device delay


def test_spans_stamped_through_batching_path():
    """submit(span=) gets queue_wait/staging_write/device stages stamped by
    the dispatcher and fetcher threads before the future resolves."""
    from tensorflow_web_deploy_tpu.utils.tracing import Span

    eng = FakeStagingEngine(bucket=4)
    b = Batcher(eng, max_batch=4, max_delay_ms=5)
    b.start()
    span = Span("batch-span")
    b.submit(_canvas(1), (2, 2), span=span).result(timeout=5)
    b.stop()
    assert {"queue_wait", "staging_write", "device_dispatch",
            "device_execute"} <= set(span.stages)
    assert all(v >= 0 for v in span.stages.values())
    assert span.meta["batch_bucket"] == 4


def test_stop_terminates_fetcher_when_inflight_full():
    """Shutdown with a busy fetch pipeline: the stop sentinel must be
    delivered once the fetcher drains (a dropped sentinel strands the
    thread), and every submitted request still resolves."""
    eng = FakeEngine(delay_s=0.05)
    b = Batcher(eng, max_batch=1, max_delay_ms=1, max_in_flight=1)
    b.start()
    futures = [b.submit(_canvas(i), (1, 1)) for i in range(6)]
    time.sleep(0.05)  # let the in-flight queue fill
    b.stop()
    assert not b._fetcher.is_alive()
    assert not b._sealer.is_alive()
    done = [f for f in futures if f.done()]
    for f in done:
        f.result(timeout=0)  # none should hold an exception


def test_stats_populated():
    eng = FakeEngine()
    b = Batcher(eng, max_batch=4, max_delay_ms=5)
    b.start()
    for f in [b.submit(_canvas(i), (1, 1)) for i in range(8)]:
        f.result(timeout=5)
    b.stop()
    snap = b.stats.snapshot()
    assert snap["requests_total"] == 8
    assert snap["latency_ms"]["p50"] >= 0
    assert sum(snap["batch_size_histogram"].values()) == 8


def test_adaptive_delay_bounds_and_response_to_depth():
    """The live window stays inside [0, max_delay_ms]: it grows toward the
    cap under backlog (outstanding leased slots) and decays toward 0 when
    nothing is assembling."""
    b = Batcher(FakeEngine(), max_batch=8, max_delay_ms=10, adaptive_delay=True)
    assert b.current_delay_ms == 0.0  # idle start: dispatch immediately

    # Backlog: outstanding leased slots (sealer not started — deterministic).
    b._pending_slots = 16
    for _ in range(100):
        d = b._update_delay()
        assert 0.0 <= d <= b.max_delay_s
    assert b.current_delay_ms > 9.0  # converged toward the cap

    # Drain: no outstanding slots pulls the window back toward zero.
    b._pending_slots = 0
    for _ in range(100):
        d = b._update_delay()
        assert 0.0 <= d <= b.max_delay_s
    assert b.current_delay_ms < 0.1


def test_adaptive_delay_disabled_pins_cap():
    b = Batcher(FakeEngine(), max_batch=8, max_delay_ms=7, adaptive_delay=False)
    assert b._update_delay() == pytest.approx(7e-3)
    assert b.current_delay_ms == pytest.approx(7.0)


def test_deadlines_and_latencies_survive_wall_clock_jumps(monkeypatch):
    """Batcher arithmetic runs on time.monotonic: a wall-clock step (NTP,
    manual set) while requests are in flight must corrupt neither the
    batching window nor recorded latencies."""
    eng = FakeEngine(delay_s=0.01)
    b = Batcher(eng, max_batch=4, max_delay_ms=10)
    b.start()
    # Wall clock jumps a year into the future mid-run; monotonic is immune.
    monkeypatch.setattr(time, "time", lambda: 4e9)
    futures = [b.submit(_canvas(i), (1, 1)) for i in range(8)]
    for f in futures:
        f.result(timeout=5)
    b.stop()
    snap = b.stats.snapshot()
    assert snap["requests_total"] == 8
    # A time.time()-based path would record ~4e9-second latencies here.
    assert 0 <= snap["latency_ms"]["p99"] < 5_000
    assert 0 <= snap["uptime_s"] < 3600


def test_occupancy_recorded_per_batch():
    """Each dispatch records real/bucket rows; with a FakeEngine (no
    staging API) the bucket is the batch size, so occupancy is 1.0."""
    eng = FakeEngine()
    b = Batcher(eng, max_batch=4, max_delay_ms=5)
    b.start()
    for f in [b.submit(_canvas(i), (1, 1)) for i in range(8)]:
        f.result(timeout=5)
    b.stop()
    snap = b.stats.snapshot()
    assert snap["batch_occupancy"] == pytest.approx(1.0)
    assert snap["batches_dispatched"] >= 1


class FakeStagingEngine(FakeEngine):
    """FakeEngine + the staging API the real engine exposes — verifies the
    batcher row-stages (write_row per request, one dispatch per slab)."""

    class Slab:
        def __init__(self, bucket, row_shape):
            self.bucket = bucket
            self.canvases = np.zeros((bucket, *row_shape), np.uint8)
            self.hws = np.ones((bucket, 2), np.int32)
            self.writes = 0

        def write_row(self, i, canvas, hw):
            self.canvases[i] = canvas
            self.hws[i] = hw
            self.writes += 1

    def __init__(self, bucket=4, **kw):
        super().__init__(**kw)
        self.bucket = bucket
        self.slabs = []

    def acquire_staging(self, n, row_shape):
        slab = self.Slab(max(n, self.bucket), row_shape)
        self.slabs.append(slab)
        return slab

    def dispatch_staged(self, slab, n):
        self.batches.append(n)
        return slab.canvases[:n].copy(), slab.hws[:n].copy()


def test_batcher_uses_staging_api_when_available():
    eng = FakeStagingEngine(bucket=4)
    b = Batcher(eng, max_batch=4, max_delay_ms=5)
    b.start()
    futures = [b.submit(_canvas(i), (i, i)) for i in range(6)]
    results = [f.result(timeout=5)[0] for f in futures]
    b.stop()
    assert results == [i + 2 * i for i in range(6)]
    assert eng.slabs  # staged path taken, not np.stack
    assert sum(s.writes for s in eng.slabs) == 6  # one row write per request
    # occupancy reflects real/bucket (6 real rows over ≥4-row slabs)
    assert 0 < b.stats.snapshot()["batch_occupancy"] <= 1.0


def test_submit_after_stop_fails_fast_with_shutting_down():
    """Post-shutdown submits must resolve immediately with ShuttingDown
    (mapped to 503 by the HTTP layer), never strand the caller."""
    from tensorflow_web_deploy_tpu.serving.batcher import ShuttingDown

    b = Batcher(FakeEngine(), max_batch=4, max_delay_ms=1)
    b.start()
    b.stop()
    f = b.submit(_canvas(1), (8, 8))
    with pytest.raises(ShuttingDown):
        f.result(timeout=1)


# ----------------------------------------------------------- slot leasing


class FakeSlotEngine(FakeEngine):
    """FakeEngine + REAL StagingSlab objects speaking the full slot-lease
    API (row views, write_hw, lease refcount) — exercises decode-into-slab
    assembly without jax."""

    supports_slot_lease = True

    def __init__(self, bucket=4, **kw):
        super().__init__(**kw)
        self.bucket = bucket
        self.slabs = []
        self.recycled = []

    def acquire_staging(self, n, row_shape):
        from tensorflow_web_deploy_tpu.serving.engine import StagingSlab

        slab = StagingSlab(tuple(row_shape), max(n, self.bucket), packed=False)
        slab.arm(self.recycled.append)
        self.slabs.append(slab)
        return slab

    def release_staging(self, slab):
        slab.finish_fetch()

    def dispatch_staged(self, slab, n):
        self.batches.append(n)
        return slab, slab.canvases[:n].copy(), slab.hws[:n].copy()

    def fetch_outputs(self, handle):
        slab, canvases, hws = handle
        try:
            return super().fetch_outputs((canvases, hws))
        finally:
            slab.finish_fetch()


def test_lease_row_is_slab_memory():
    """The leased row IS the slab's memory — decoding into it stages the
    image with zero further copies (the tentpole's 2-copies→1 criterion,
    asserted on buffer identity)."""
    eng = FakeSlotEngine(bucket=4)
    b = Batcher(eng, max_batch=4, max_delay_ms=5)
    b.start()
    try:
        lease = b.lease((8, 8, 3))
        slab = lease.builder.slab
        assert lease.row is not None and lease.row.base is not None
        assert np.shares_memory(lease.row, slab.canvases)
        # write like the native decoder would: straight into the view
        lease.row[:] = 7
        assert (slab.canvases[lease.index] == 7).all()
        lease.commit((8, 8))
        out = lease.future.result(timeout=5)[0]
        assert out == 7 + 16  # tag 7 + hw sum — staged bytes reached dispatch
    finally:
        b.stop()


def test_released_slot_becomes_padded_hole():
    """A lease released mid-assembly (decode failure) leaves a hole: the
    batch dispatches without it, the committed siblings' results route
    correctly, and the hole's row is padded hw=1×1."""
    eng = FakeSlotEngine(bucket=4)
    b = Batcher(eng, max_batch=4, max_delay_ms=20)
    b.start()
    try:
        l0 = b.lease((8, 8, 3))
        l1 = b.lease((8, 8, 3))
        l2 = b.lease((8, 8, 3))
        slab = l0.builder.slab
        for lease, tag in ((l0, 3), (l2, 9)):
            lease.row[:] = tag
        l1.release()  # e.g. the upload 400d mid-decode
        l0.commit((2, 2))
        l2.commit((4, 4))
        assert l0.future.result(timeout=5)[0] == 3 + 4
        assert l2.future.result(timeout=5)[0] == 9 + 8
        assert list(slab.hws[1]) == [1, 1]  # the hole was padded
        assert b.builder_stats()["holes_total"] == 1
    finally:
        b.stop()


def test_lease_timeout_expires_slot_and_batch_proceeds():
    """A lessee that never commits (dead worker) is force-expired after the
    lease timeout: its future fails with LeaseExpired and the committed
    sibling still gets its result."""
    from tensorflow_web_deploy_tpu.serving.batcher import LeaseExpired

    eng = FakeSlotEngine(bucket=4)
    b = Batcher(eng, max_batch=4, max_delay_ms=1, lease_timeout_s=0.05)
    b.start()
    try:
        good = b.lease((8, 8, 3))
        dead = b.lease((8, 8, 3))  # never committed nor released
        good.row[:] = 5
        good.commit((1, 1))
        assert good.future.result(timeout=5)[0] == 5 + 2
        with pytest.raises(LeaseExpired):
            dead.future.result(timeout=5)
        assert b.builder_stats()["lease_timeouts_total"] == 1
    finally:
        b.stop()


def test_all_holes_builder_discards_slab_without_dispatch():
    """A builder whose every slot was released dispatches nothing and its
    slab goes straight back to the pool."""
    eng = FakeSlotEngine(bucket=4)
    b = Batcher(eng, max_batch=4, max_delay_ms=1)
    b.start()
    try:
        l0 = b.lease((8, 8, 3))
        l1 = b.lease((8, 8, 3))
        l0.release()
        l1.release()
        deadline = time.monotonic() + 5
        while not eng.recycled and time.monotonic() < deadline:
            time.sleep(0.005)
        assert eng.recycled  # slab recycled, never dispatched
        assert not eng.batches
        # discarded builders still count as sealed (the /metrics contract)
        assert b.builder_stats()["batches_sealed_total"] == 1
    finally:
        b.stop()


def test_lease_blocks_at_outstanding_slot_cap():
    """lease() exerts backpressure: at the outstanding-slot cap it blocks
    until dispatches drain, instead of growing host memory without bound."""
    eng = FakeSlotEngine(bucket=2)
    b = Batcher(eng, max_batch=2, max_delay_ms=1, max_in_flight=1)
    b.start()  # cap = max_batch * max(2, max_in_flight) = 4
    try:
        # Hold the pipeline: leases never committed stay outstanding.
        held = [b.lease((8, 8, 3)) for _ in range(4)]
        t0 = time.monotonic()
        late = {}

        def blocked_lease():
            lease = b.lease((8, 8, 3))
            late["waited"] = time.monotonic() - t0
            lease.commit((1, 1))

        t = threading.Thread(target=blocked_lease)
        t.start()
        time.sleep(0.05)
        assert "waited" not in late  # still blocked at the cap
        for lease in held:
            lease.release()  # free slots
        t.join(timeout=5)
        assert late["waited"] >= 0.04
    finally:
        b.stop()


def test_padding_waste_counters_per_bucket():
    """The device-economics padding block (ROADMAP item 5: "measure it
    first"): every dispatched batch records real rows vs compiled-bucket
    rows AND real image pixels vs shipped canvas pixels, per (canvas,
    batch-bucket)."""
    eng = FakeSlotEngine(bucket=4)
    b = Batcher(eng, max_batch=4, max_delay_ms=5)
    b.start()
    try:
        # Three 4×4 images on an 8×8 canvas: whatever way the batcher
        # splits them into batches, the real-row and real-pixel totals are
        # invariant; the dispatched totals scale with the 4-row bucket.
        futures = [b.submit(_canvas(i), (4, 4)) for i in range(3)]
        for f in futures:
            f.result(timeout=5)
        pad = b.builder_stats()["padding"]
    finally:
        b.stop()
    assert set(pad) == {"8x4"}
    cell = pad["8x4"]
    assert cell["canvas"] == 8 and cell["batch_bucket"] == 4
    assert cell["rows_real"] == 3
    assert cell["rows_dispatched"] == cell["batches"] * 4
    assert cell["px_real"] == 3 * 4 * 4
    assert cell["px_dispatched"] == cell["batches"] * 4 * 8 * 8
    assert cell["padded_rows_fraction"] == pytest.approx(
        1 - 3 / (cell["batches"] * 4))
    assert 0 < cell["padded_px_fraction"] < 1


# ------------------------------------------------------ lifecycle counters


def _flat(life: dict) -> dict:
    """A lifecycle block as one flat dict of numbers."""
    return {**{k: v for k, v in life.items() if k != "by_reason"},
            **{f"by_reason.{k}": v for k, v in life["by_reason"].items()}}


def _union_s(intervals) -> float:
    total, end = 0.0, float("-inf")
    for a, b in sorted(intervals):
        if b > end:
            total += b - max(a, end)
            end = b
    return total


class _Buf:
    """A device array whose readiness the test (or FlightEngine's device
    thread) controls."""

    def __init__(self, done: bool = False):
        self.done = threading.Event()
        if done:
            self.done.set()

    def is_ready(self):
        return self.done.is_set()

    def block_until_ready(self):
        assert self.done.wait(timeout=10)
        return self


class FlightEngine(FakeSlotEngine):
    """FakeSlotEngine whose calls fly through the engine's real FlightLog:
    each dispatch puts one input buffer and gets one output buffer back,
    fakes whose readiness stands for the copy's end and the outputs'.

    With ``dev_s`` set, a copy thread lands the copies one after another,
    each ``copy_s`` after the later of its dispatch and the copy before (one
    DMA stream), and a device thread runs the calls in order, each ``dev_s``
    from the later of its copy's end and the call before. Without it, the
    test releases each call's ``(copy, ready)`` pair from ``calls`` itself."""

    supports_span_tracing = True

    def __init__(self, bucket=2, dev_s=None, copy_s=0.0, fail_fetch=False):
        super().__init__(bucket=bucket)
        from tensorflow_web_deploy_tpu.serving.engine import FlightLog

        self.log = FlightLog(threading.Lock(), "test-h2d-watch")
        self.calls = queue.Queue()
        self.dev_s, self.copy_s, self.fail_fetch = dev_s, copy_s, fail_fetch
        if dev_s is not None:
            self.copies = queue.Queue()
            threading.Thread(target=self._copy, daemon=True).start()
            threading.Thread(target=self._device, daemon=True).start()

    def _copy(self):
        while True:
            copy = self.copies.get()
            time.sleep(self.copy_s)
            copy.done.set()

    def _device(self):
        while True:
            copy, ready = self.calls.get()
            copy.done.wait()
            time.sleep(self.dev_s)
            ready.done.set()

    def dispatch_staged(self, slab, n, spans=(), rec=None):
        handle = super().dispatch_staged(slab, n)
        f = self.log.start(rec, "c8 b2", 1000)
        copy, ready = _Buf(), _Buf()
        self.log.copying(f, [copy])
        self.log.enqueued(f)
        if self.dev_s is not None:
            self.copies.put(copy)
        self.calls.put((copy, ready))
        return handle, f, ready

    def fetch_outputs(self, handle, rec=None):
        handle, f, ready = handle
        self.log.land(f, [ready])
        if self.fail_fetch:
            handle[0].finish_fetch()
            raise RuntimeError("the outputs' copy failed")
        return super().fetch_outputs(handle)


PHASES = ("h2d_s_total", "device_queue_s_total", "device_s_total", "d2h_s_total")


def _phases(rec):
    return (rec["t_h2d_done"] - rec["t_launch"], rec["t_dev_start"] - rec["t_h2d_done"],
            rec["t_ready"] - rec["t_dev_start"], rec["t_done"] - rec["t_ready"])


def _settle(b):
    deadline = time.monotonic() + 2
    while b.inflight_batches and time.monotonic() < deadline:
        time.sleep(0.001)


def test_lifecycle_counters_never_decrease_and_reasons_sum_to_batches():
    """Read while a dozen batches of every seal reason but drain go by:
    each counter only grows, and the per-reason counts sum to the total."""
    eng = FlightEngine(bucket=4, dev_s=0.01, copy_s=0.005)
    b = Batcher(eng, max_batch=4, max_delay_ms=15, adaptive_delay=False, pipeline_depth=2)
    b.start()
    reads, stop = [b.lifecycle_stats()], threading.Event()

    def reader():
        while not stop.is_set():
            reads.append(b.lifecycle_stats())
            time.sleep(0.002)

    t = threading.Thread(target=reader)
    t.start()
    try:
        futures = [b.submit(_canvas(i), (1, 1)) for i in range(16)]     # four full batches
        for f in futures:
            f.result(timeout=5)
        for i in range(3):                                              # three sealed by the window
            b.submit(_canvas(i), (1, 1)).result(timeout=5)
        b.submit(_canvas(1), (1, 1), bulk=True)                         # one flushed
        b.flush_bulk()
        time.sleep(0.1)
    finally:
        stop.set()
        t.join(timeout=5)
        b.stop()
    reads.append(b.lifecycle_stats())
    for before, after in zip(reads, reads[1:]):
        a, z = _flat(before), _flat(after)
        assert set(a) == set(z)
        assert all(z[k] >= a[k] for k in a), (before, after)
    last = reads[-1]
    assert last["batches_total"] == sum(last["by_reason"].values()) == len(eng.batches)
    assert last["by_reason"]["full"] == 4 and last["by_reason"]["window"] == 3
    assert last["by_reason"]["flush"] == 1 and last["by_reason"]["drain"] == 0
    assert set(last["by_reason"]) == {"full", "arena", "window", "flush", "drain"}
    assert {r["reason"] for r in b.batch_timeline()} == {"full", "window", "flush"}


def test_starved_clock_plus_inflight_union_is_elapsed_time_and_phases_tile_a_batch():
    """A scripted sequence: idle, one batch, idle, three batches that
    overlap in flight, idle. Between two reads of the block, the starved
    seconds plus the union of the batches' [t_launch, t_done] is the time
    between the reads; each batch's phases sum to t_done - t_open, and its
    flight's four (copy, wait behind the calls before, device, copy back)
    to t_done - t_launch, one by one and in the totals."""
    eng = FlightEngine(bucket=2, dev_s=0.03, copy_s=0.01)
    b = Batcher(eng, max_batch=2, max_delay_ms=2, adaptive_delay=False, pipeline_depth=3)
    b.start()
    try:
        first = b.lifecycle_stats()
        time.sleep(0.05)
        b.submit(_canvas(1), (1, 1)).result(timeout=5)
        time.sleep(0.04)
        mid = b.lifecycle_stats()
        assert mid["starved_s_total"] > first["starved_s_total"] + 0.08
        futures = [b.submit(_canvas(i), (1, 1)) for i in range(6)]     # three full batches, back to back
        for f in futures:
            f.result(timeout=5)
        time.sleep(0.03)
        _settle(b)     # every batch is done once its last future resolved and _batch_done ran
        last = b.lifecycle_stats()
    finally:
        b.stop()
    recs = b.batch_timeline()
    assert len(recs) == 4 and all(r["t_done"] is not None for r in recs)
    flights = [(r["t_launch"], r["t_done"]) for r in recs]
    elapsed = last["now_s"] - first["now_s"]
    starved = last["starved_s_total"] - first["starved_s_total"]
    assert starved + _union_s(flights) == pytest.approx(elapsed, abs=1e-3)
    assert _union_s(flights[1:]) < sum(z - a for a, z in flights[1:])     # they did overlap
    # the phases tile a batch, one by one and in the totals
    phases = ("open_s_total", "launch_wait_s_total", *PHASES)
    assert sum(last[k] - first[k] for k in phases) == pytest.approx(
        sum(r["t_done"] - r["t_open"] for r in recs), abs=1e-6)
    for r in recs:
        assert all(p >= 0 for p in _phases(r)), r
        assert sum(_phases(r)) == pytest.approx(r["t_done"] - r["t_launch"], abs=1e-9)
        assert r["t_launched"] <= r["t_fetch"] <= r["t_done"]
    for k, i in zip(PHASES, range(4)):
        assert last[k] - first[k] == pytest.approx(sum(_phases(r)[i] for r in recs), abs=1e-9)
    # the device ran four calls of 30 ms one after another; the three that
    # came together waited behind each other
    # (each stamp lands a thread's wake-up after its event: give the
    # device phase 5 ms a call either way)
    assert 4 * 0.025 <= last["device_s_total"] - first["device_s_total"] < 4 * 0.03 + 0.05
    assert last["device_queue_s_total"] - first["device_queue_s_total"] >= 0.05
    assert all(r["late"] == () for r in recs) and last["stamps_late_total"] == 0, [r["late"] for r in recs]


def test_a_call_starts_on_the_device_when_its_copy_and_the_call_before_are_done():
    """``t_dev_start`` is the later of the copy's end and the previous
    call's ``t_ready``: batch 2's copy lands while batch 1 computes, so it
    waits for batch 1; batch 3's lands after batch 2 is done, so it starts
    with its copy. The test releases each event itself."""
    eng = FlightEngine(bucket=1)
    b = Batcher(eng, max_batch=1, max_delay_ms=1, adaptive_delay=False, pipeline_depth=3)
    b.start()
    try:
        futures = [b.submit(_canvas(i), (1, 1)) for i in range(2)]
        (copy1, ready1), (copy2, ready2) = eng.calls.get(timeout=5), eng.calls.get(timeout=5)
        time.sleep(0.02)
        copy1.done.set()
        time.sleep(0.02)
        copy2.done.set()         # lands while batch 1 is on the device
        time.sleep(0.03)
        ready1.done.set()
        time.sleep(0.02)
        ready2.done.set()
        for f in futures:
            f.result(timeout=5)
        time.sleep(0.03)
        f3 = b.submit(_canvas(3), (1, 1))
        copy3, ready3 = eng.calls.get(timeout=5)
        time.sleep(0.02)
        copy3.done.set()
        time.sleep(0.02)
        ready3.done.set()
        f3.result(timeout=5)
        _settle(b)
        life = b.lifecycle_stats()
    finally:
        b.stop()
    r1, r2, r3 = b.batch_timeline()
    assert r1["t_dev_start"] == r1["t_h2d_done"]                 # the device was free
    assert r2["t_h2d_done"] < r1["t_ready"] == r2["t_dev_start"]  # it waited for batch 1
    assert r2["t_dev_start"] - r2["t_h2d_done"] == pytest.approx(0.03, abs=0.015)
    assert r3["t_h2d_done"] > r2["t_ready"] and r3["t_dev_start"] == r3["t_h2d_done"]
    for r in (r1, r2, r3):
        assert r["t_ready"] - r["t_dev_start"] == pytest.approx(0.02, abs=0.015) or r is r1
        assert r["late"] == ()
    assert life["device_queue_s_total"] == pytest.approx(r2["t_dev_start"] - r2["t_h2d_done"], abs=1e-9)


def test_the_h2d_bound_clock_runs_only_while_a_copy_flies_and_the_device_has_no_call():
    """Batch 1's copy flies 40 ms alone (the clock runs), then batch 1 is on
    the device while batch 2's copy flies (it stops), then batch 2's copy
    flies on alone after batch 1 is done (it runs again). The clock is the
    measure of the copies' union less the device phases', from the stamps."""
    eng = FlightEngine(bucket=1)
    b = Batcher(eng, max_batch=1, max_delay_ms=1, adaptive_delay=False, pipeline_depth=3)
    b.start()
    try:
        first = b.lifecycle_stats()
        f1 = b.submit(_canvas(1), (1, 1))
        copy1, ready1 = eng.calls.get(timeout=5)
        time.sleep(0.04)
        copy1.done.set()
        f2 = b.submit(_canvas(2), (1, 1))
        copy2, ready2 = eng.calls.get(timeout=5)
        time.sleep(0.03)
        ready1.done.set()
        f1.result(timeout=5)
        time.sleep(0.03)
        copy2.done.set()
        time.sleep(0.01)
        ready2.done.set()
        f2.result(timeout=5)
        _settle(b)
        last = b.lifecycle_stats()
    finally:
        b.stop()
    r1, r2 = b.batch_timeline()
    copies = [(r["t_launch"], r["t_h2d_done"]) for r in (r1, r2)]
    device = [(r["t_dev_start"], r["t_ready"]) for r in (r1, r2)]
    # copies less device phases: [t_launch1, t_h2d1] and [t_ready1, t_h2d2]
    want = (r1["t_h2d_done"] - r1["t_launch"]) + (r2["t_h2d_done"] - r1["t_ready"])
    assert _union_s(copies) - _union_s([(max(a, c), min(b_, d)) for a, b_ in copies
                                        for c, d in device if max(a, c) < min(b_, d)]) \
        == pytest.approx(want, abs=1e-9)
    bound = last["h2d_bound_s_total"] - first["h2d_bound_s_total"]
    assert bound == pytest.approx(want, abs=1e-9)
    assert 0.04 + 0.03 <= bound < last["h2d_s_total"] - first["h2d_s_total"]


def test_a_stamp_taken_after_its_event_is_counted_late():
    """A copy that had landed before the watcher turned to it, and outputs
    computed before the completion thread turned to them: both stamps are
    upper bounds, named in the record and counted."""
    eng = FlightEngine(bucket=1)
    b = Batcher(eng, max_batch=1, max_delay_ms=1, adaptive_delay=False)
    b.start()
    try:
        f = b.submit(_canvas(1), (1, 1))
        copy, ready = eng.calls.get(timeout=5)
        copy.done.set()
        time.sleep(0.03)
        f2 = b.submit(_canvas(2), (1, 1))   # on time: the threads were waiting
        copy2, ready2 = eng.calls.get(timeout=5)
        time.sleep(0.02)
        copy2.done.set()
        time.sleep(0.02)
        ready.done.set()
        f.result(timeout=5)
        time.sleep(0.02)
        ready2.done.set()
        f2.result(timeout=5)
        _settle(b)
        life = b.lifecycle_stats()
    finally:
        b.stop()
    r1, r2 = b.batch_timeline()
    assert r2["late"] == ()
    # the watcher needs no time to see a landed copy: the first copy is late
    # only if it had landed when the watcher turned to it, which the test
    # cannot force; the count is the records' own
    assert life["stamps_late_total"] == len(r1["late"]) + len(r2["late"])

    # a copy landed and outputs computed before anyone asked: both late
    class AtOnce(FlightEngine):
        def dispatch_staged(self, slab, n, spans=(), rec=None):
            handle = FakeSlotEngine.dispatch_staged(self, slab, n)
            f = self.log.start(rec, "c8 b1", 1000)
            self.log.copying(f, [_Buf(done=True)])
            self.log.enqueued(f)
            time.sleep(0.05)
            return handle, f, _Buf(done=True)

    b2 = Batcher(AtOnce(bucket=1), max_batch=1, max_delay_ms=1, adaptive_delay=False)
    b2.start()
    try:
        b2.submit(_canvas(1), (1, 1)).result(timeout=5)
        _settle(b2)
        life2 = b2.lifecycle_stats()
    finally:
        b2.stop()
    (rec,) = b2.batch_timeline()
    assert set(rec["late"]) == {"t_h2d_done", "t_ready"} and life2["stamps_late_total"] == 2
    assert sum(_phases(rec)) == pytest.approx(rec["t_done"] - rec["t_launch"], abs=1e-9)


@pytest.mark.parametrize("where", ["dispatch", "fetch"])
def test_a_failed_dispatch_or_fetch_adds_to_no_phase(where):
    """A batch whose dispatch raised, or whose fetch raised after its
    outputs were computed, counts as a batch and adds to none of the four
    phases or the h2d-bound clock."""
    eng = FlightEngine(bucket=1, dev_s=0.01, copy_s=0.01, fail_fetch=where == "fetch")
    if where == "dispatch":
        def no_device(slab, n, spans=(), rec=None):
            raise RuntimeError("no device")
        eng.dispatch_staged = no_device
    b = Batcher(eng, max_batch=1, max_delay_ms=1, adaptive_delay=False)
    b.start()
    try:
        first = b.lifecycle_stats()
        with pytest.raises(RuntimeError):
            b.submit(_canvas(1), (1, 1)).result(timeout=5)
        _settle(b)
        last = b.lifecycle_stats()
    finally:
        b.stop()
    (rec,) = b.batch_timeline()
    assert last["batches_total"] == 1
    for k in (*PHASES, "h2d_bound_s_total", "stamps_late_total"):
        assert last[k] == first[k], k
    assert (rec["t_ready"] is not None) == (where == "fetch")


def test_a_flight_log_stamps_the_calls_before_a_landed_one_late():
    """The device runs its calls in order: when call 2's outputs are
    computed and call 1 is unstamped yet (its thread has not run), call 1 is
    stamped then, late, and so is a copy whose thread has not stamped it."""
    from tensorflow_web_deploy_tpu.serving.engine import FlightLog

    log = FlightLog(threading.Lock(), "test-h2d-watch")
    recs = [{"seq": i, "rows": 1, "t_h2d_done": None, "t_dev_start": None,
             "t_ready": None, "late": ()} for i in (1, 2)]
    flights = [log.start(r, "c8 b1", 10) for r in recs]
    bufs = [(_Buf(), _Buf()) for _ in flights]
    for f, (copy, _) in zip(flights, bufs):
        log.copying(f, [copy])
        log.enqueued(f)
    time.sleep(0.02)        # the copies' threads are waiting
    bufs[0][0].done.set()
    time.sleep(0.02)
    assert recs[0]["t_h2d_done"] is not None and recs[0]["late"] == ()
    bufs[1][0].done.set()
    bufs[0][1].done.set()
    bufs[1][1].done.set()
    log.land(flights[1], [bufs[1][1]])
    log.land(flights[0], [bufs[0][1]])       # already stamped by call 2's landing
    one, two = recs
    assert one["t_ready"] == two["t_ready"] and "t_ready" in one["late"]
    assert two["t_dev_start"] == max(two["t_h2d_done"], one["t_ready"])
    assert "t_ready" in two["late"]          # its outputs were computed before land() asked
    assert flights[0].prev is None and flights[1].prev is None
    # copies land out of order: each has a thread of its own, so neither is late
    late_first = [log.start({"seq": i, "rows": 1, "t_h2d_done": None, "t_dev_start": None,
                             "t_ready": None, "late": ()}, "c8 b1", 10) for i in (3, 4)]
    copies = [_Buf(), _Buf()]
    for f, copy in zip(late_first, copies):
        log.copying(f, [copy])
    time.sleep(0.02)
    copies[1].done.set()
    time.sleep(0.02)
    copies[0].done.set()
    time.sleep(0.02)
    assert late_first[0].late == late_first[1].late == ()
    assert late_first[1].t_h2d_done < late_first[0].t_h2d_done


@pytest.mark.parametrize("says", [None, "odd", "all"])
def test_unpack_kernel_batches_are_counted_from_the_batch_record(says):
    """``unpack_kernel_batches_total`` adds up what the engine notes in the
    batch record beside ``h2d_bytes``: nothing for an engine that takes no
    record (or whose unpack is the XLA gather, as on the CPU), every batch
    or every other one for an engine that says its unpack ran the kernel."""
    class Noting(FakeSlotEngine):
        supports_span_tracing = says is not None

        def dispatch_staged(self, slab, n, spans=(), rec=None):
            if rec is not None:
                rec["h2d_bytes"] = 1000
                rec["unpack_kernel"] = says == "all" or rec["seq"] % 2 == 1
            return super().dispatch_staged(slab, n)

        def fetch_outputs(self, handle, rec=None):
            return super().fetch_outputs(handle)

    eng = Noting(bucket=2)
    b = Batcher(eng, max_batch=2, max_delay_ms=2, adaptive_delay=False)
    b.start()
    try:
        assert b.lifecycle_stats()["unpack_kernel_batches_total"] == 0
        for i in range(6):  # one at a time: six batches
            b.submit(_canvas(i), (1, 1)).result(timeout=5)
        deadline = time.monotonic() + 2
        while b.inflight_batches and time.monotonic() < deadline:
            time.sleep(0.001)
        life = b.lifecycle_stats()
    finally:
        b.stop()
    recs = b.batch_timeline()
    assert life["batches_total"] == len(recs) == 6
    want = {None: 0, "all": 6, "odd": sum(r["seq"] % 2 for r in recs)}[says]
    assert life["unpack_kernel_batches_total"] == want
    assert sum(bool(r["unpack_kernel"]) for r in recs) == want
    assert life["h2d_bytes_total"] == (0 if says is None else 6000)


def test_a_failed_dispatch_still_closes_its_lifecycle():
    class FailingDispatch(FakeEngine):
        def dispatch_batch(self, canvases, hws):
            raise RuntimeError("no device")

    b = Batcher(FailingDispatch(), max_batch=2, max_delay_ms=1)
    b.start()
    try:
        with pytest.raises(RuntimeError, match="no device"):
            b.submit(_canvas(1), (1, 1)).result(timeout=5)
        time.sleep(0.02)
        life = b.lifecycle_stats()
        time.sleep(0.02)
        later = b.lifecycle_stats()
    finally:
        b.stop()
    (rec,) = b.batch_timeline()
    assert rec["t_launched"] == rec["t_done"] and rec["t_fetch"] is None
    assert life["batches_total"] == 1 and b.inflight_batches == 0
    # nothing is launched any more: the starved clock runs again
    assert later["starved_s_total"] - life["starved_s_total"] == pytest.approx(
        later["now_s"] - life["now_s"], abs=1e-6)


def test_an_engines_ceiling_on_calls_in_flight_holds_over_all_buckets():
    """Three full batches of two canvas buckets back to back at pipeline
    depth 3: they overlap in flight, and with the engine's own ceiling of
    one call (what its device's memory holds beside the weights) they do
    not, though each bucket's depth would let them."""
    def flights(ceiling):
        eng = FakeSlotEngine(bucket=2, delay_s=0.03)
        eng.max_calls_in_flight = ceiling
        b = Batcher(eng, max_batch=2, max_delay_ms=2, adaptive_delay=False, pipeline_depth=3)
        b.start()
        try:
            futures = [b.submit(_canvas(i, size=8 if i < 4 else 16), (1, 1)) for i in range(6)]
            for f in futures:
                f.result(timeout=5)
            deadline = time.monotonic() + 2
            while b.inflight_batches and time.monotonic() < deadline:
                time.sleep(0.001)
        finally:
            b.stop()
        return [(r["t_launch"], r["t_done"]) for r in b.batch_timeline()]

    free, held = flights(None), flights(1)
    assert len(free) == len(held) == 3
    assert _union_s(free) < sum(z - a for a, z in free) - 0.02          # they did overlap
    assert _union_s(held) == pytest.approx(sum(z - a for a, z in held), abs=2e-3)


def test_a_builder_holds_no_more_rows_than_the_engine_allows_at_its_canvas():
    """A ceiling that goes with the canvas (a token decoder's is in token
    slots): eight rows of the small canvas a batch, two of the large."""
    eng = FakeEngine(delay_s=0.02)
    eng.max_rows = lambda canvas_s: 8 if canvas_s <= 8 else 2
    b = Batcher(eng, max_batch=8, max_delay_ms=30, adaptive_delay=False)
    b.start()
    small = [b.submit(_canvas(i, size=8), (1, 1)) for i in range(8)]
    large = [b.submit(_canvas(i, size=16), (1, 1)) for i in range(6)]
    for f in small + large:
        f.result(timeout=5)
    b.stop()
    assert sorted(eng.batches) == [2, 2, 2, 8]


def _sealed_waiting(b: Batcher) -> int:
    with b._cond:
        return sum(not c.bulk for c in b._closing)


def test_under_a_ceiling_of_one_call_builders_past_their_window_keep_accepting():
    """One call in flight at a time, three canvas buckets, a slow call.
    Pages come into all three while a call runs: when it is done the oldest
    builder takes the slot and the other two keep accepting, so no more than
    one batch is ever sealed and waiting and the calls that follow are
    fuller. Sealing all three when the slot frees (the rule before) ran
    seven calls of 1, 2, 2, 2, 2, 2, 2 rows here."""
    eng = FakeEngine(delay_s=0.3)
    eng.max_calls_in_flight = 1
    b = Batcher(eng, max_batch=8, max_delay_ms=5, adaptive_delay=False, pipeline_depth=4)
    b.start()
    seen, stop = [], threading.Event()

    def watch():
        while not stop.is_set():
            seen.append(_sealed_waiting(b))
            time.sleep(0.002)

    t = threading.Thread(target=watch)
    t.start()
    try:
        futures = [b.submit(_canvas(0), (1, 1))]                          # call 1, alone
        time.sleep(0.05)
        for i in range(6):                                                # 2 a canvas, during call 1
            futures.append(b.submit(_canvas(i, size=(8, 12, 16)[i % 3]), (1, 1)))
        time.sleep(0.4)                                                   # call 2 runs: the canvas-8 pair
        for i in range(6):                                                # 2 a canvas more
            futures.append(b.submit(_canvas(i, size=(8, 12, 16)[i % 3]), (1, 1)))
        with b._cond:
            growing = {c.key[0]: (len(c.leases), c.accepting) for c in b._open.values()}
        for f in futures:
            f.result(timeout=10)
    finally:
        stop.set()
        t.join(timeout=5)
        b.stop()
    assert growing == {8: (2, True), 12: (4, True), 16: (4, True)}
    assert max(seen) <= 1
    recs = b.batch_timeline()
    assert [(r["key"][0], r["rows"]) for r in recs] == [(8, 1), (8, 2), (12, 4), (16, 4), (8, 2)]
    assert eng.batches == [1, 2, 4, 4, 2]
    assert {r["reason"] for r in recs} == {"window"}
    assert b.lifecycle_stats()["window_holds_total"] > 0


@pytest.mark.parametrize("ceiling", [1, None])
def test_a_builder_past_its_window_goes_before_full_batches_opened_after_it(ceiling):
    """A canvas-8 builder passes its window while one call runs; then three
    full batches of canvas 16 (two rows each) seal. Under a ceiling of one
    call the builder is the oldest, so it takes the next slot ahead of them
    (the rule before sent it after all three), and the ceiling held it
    open meanwhile. With no ceiling nothing is held: every batch goes
    when it seals, in the same order with the same rows and reasons."""
    eng = FakeEngine(delay_s=0.25)
    eng.max_calls_in_flight = ceiling
    eng.max_rows = lambda canvas_s: 8 if canvas_s <= 8 else 2
    b = Batcher(eng, max_batch=8, max_delay_ms=5, adaptive_delay=False, pipeline_depth=4)
    b.start()
    try:
        futures = [b.submit(_canvas(0), (1, 1))]
        time.sleep(0.03)
        futures.append(b.submit(_canvas(1), (1, 1)))
        time.sleep(0.03)
        futures += [b.submit(_canvas(i, size=16), (1, 1)) for i in range(6)]
        for f in futures:
            f.result(timeout=10)
        life = b.lifecycle_stats()
    finally:
        b.stop()
    recs = b.batch_timeline()
    assert [(r["key"][0], r["rows"], r["reason"]) for r in recs] == [
        (8, 1, "window"), (8, 1, "window"), (16, 2, "full"), (16, 2, "full"), (16, 2, "full")]
    assert life["by_reason"] == {"full": 3, "arena": 0, "window": 2, "flush": 0, "drain": 0}
    assert (life["window_holds_total"] > 0) == (ceiling is not None)


def test_a_flight_log_under_contention_stamps_each_flight_once_and_in_device_order():
    """Stress: 8 threads land 200 calls of one FlightLog in a shuffled order
    while the copies' threads stamp them, with a tiny switch interval. A
    lost or doubled stamp would break the invariants: each record agrees
    with its flight, every call starts on the device no earlier than its
    copy's end and the previous call's ``t_ready``, and readiness stamps
    run in device order."""
    import random
    import sys

    from tensorflow_web_deploy_tpu.serving.engine import FlightLog

    rng = random.Random(7)
    switch = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    log = FlightLog(threading.Lock(), "test-h2d-watch")
    try:
        recs, flights, outs = [], [], []
        for i in range(200):
            rec = {"seq": i, "rows": 1, "t_h2d_done": None, "t_dev_start": None, "t_ready": None, "late": ()}
            f = log.start(rec, "c8 b1", 10)
            copy, ready = _Buf(), _Buf()
            log.copying(f, [copy])
            log.enqueued(f)
            recs.append(rec), flights.append(f), outs.append((copy, ready))
        order = list(range(200))
        rng.shuffle(order)
        work = iter(order)
        lock = threading.Lock()

        def lander():
            while True:
                with lock:
                    i = next(work, None)
                if i is None:
                    return
                outs[i][0].done.set()
                outs[i][1].done.set()
                log.land(flights[i], [outs[i][1]])

        threads = [threading.Thread(target=lander) for _ in range(8)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=30)
        assert not any(t.is_alive() for t in threads)
    finally:
        sys.setswitchinterval(switch)
    for i, (rec, f) in enumerate(zip(recs, flights)):
        assert (rec["t_h2d_done"], rec["t_dev_start"], rec["t_ready"], rec["late"]) == \
            (f.t_h2d_done, f.t_dev_start, f.t_ready, f.late)
        assert rec["t_h2d_done"] <= rec["t_dev_start"] <= rec["t_ready"]
        assert len(set(f.late)) == len(f.late) and f.prev is None
        if i:
            assert rec["t_dev_start"] == max(rec["t_h2d_done"], recs[i - 1]["t_ready"])
            assert recs[i - 1]["t_ready"] <= rec["t_ready"]
