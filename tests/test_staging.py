"""Zero-copy batch staging (engine.StagingSlab + the slab pool).

The request path's contract: each image's canvas is copied exactly once
(into its slab row), and dispatch ships the whole slab in ONE host→device
transfer from a preallocated, reused buffer — no np.stack/concatenate
full-batch copies anywhere between decode and device.
"""

import threading

import numpy as np
import pytest

from tensorflow_web_deploy_tpu.serving.batcher import Batcher
from tensorflow_web_deploy_tpu.serving.engine import InferenceEngine, StagingSlab
from tensorflow_web_deploy_tpu.utils.config import ModelConfig, ServerConfig


# ------------------------------------------------------------- slab (no jax)


def test_packed_slab_views_share_memory():
    """Row writes must land in the wire buffer itself: the canvas and hw
    trailer are views into one contiguous uint8 array."""
    slab = StagingSlab((16, 16, 3), bucket=4, packed=True)
    assert slab.buf.shape == (4, 16 * 16 * 3 + 4)
    assert np.shares_memory(slab.canvases, slab.buf)
    assert np.shares_memory(slab.trailer, slab.buf)

    canvas = np.full((16, 16, 3), 7, np.uint8)
    slab.write_row(2, canvas, (300, 200))
    row = slab.buf[2]
    assert (row[: 16 * 16 * 3] == 7).all()
    # 4-byte big-endian (h, w) trailer
    assert list(row[-4:]) == [300 >> 8, 300 & 0xFF, 200 >> 8, 200 & 0xFF]
    # untouched rows still carry the hw=(1,1) padding marker
    assert list(slab.buf[0, -4:]) == [0, 1, 0, 1]

    slab.pad_from(1)
    assert list(slab.buf[2, -4:]) == [0, 1, 0, 1]  # padded over


def test_unpacked_slab_rows():
    slab = StagingSlab((8, 8, 3), bucket=2, packed=False)
    slab.write_row(0, np.full((8, 8, 3), 9, np.uint8), (5, 6))
    assert (slab.canvases[0] == 9).all()
    assert list(slab.hws[0]) == [5, 6]
    slab.pad_from(1)
    assert list(slab.hws[1]) == [1, 1]


def test_write_rows_matches_write_row():
    a = StagingSlab((4, 4, 3), bucket=3, packed=True)
    b = StagingSlab((4, 4, 3), bucket=3, packed=True)
    rng = np.random.RandomState(0)
    canvases = rng.randint(0, 256, (3, 4, 4, 3), np.uint8)
    hws = np.array([[4, 4], [300, 2], [1, 257]], np.int32)
    a.write_rows(canvases, hws)
    for i in range(3):
        b.write_row(i, canvases[i], tuple(hws[i]))
    np.testing.assert_array_equal(a.buf, b.buf)


# ---------------------------------------------------------------- engine


@pytest.fixture(scope="module")
def staging_engine(request):
    small_cls_pb = request.getfixturevalue("small_cls_pb")
    mc = ModelConfig(
        name="small_cls", pb_path=small_cls_pb, input_size=(96, 96),
        preprocess="inception", dtype="float32",
    )
    cfg = ServerConfig(model=mc, canvas_buckets=(128,), batch_buckets=(8,))
    engine = InferenceEngine(cfg)
    engine.warmup()
    return engine


def test_slab_pool_reuses_buffers(staging_engine):
    """Sequential dispatches reuse the SAME staging buffer: after warmup,
    further batches allocate nothing new."""
    eng = staging_engine
    rng = np.random.RandomState(1)
    hws = np.full((8, 2), 128, np.int32)

    eng.run_batch(rng.randint(0, 256, (8, 128, 128, 3), np.uint8), hws)
    allocs_before = eng.staging_stats()["slab_allocs_total"]

    slab_ids = set()
    for _ in range(4):
        slab = eng.acquire_staging(8, (128, 128, 3))
        slab_ids.add(id(slab.buf))
        handle = eng.dispatch_staged(slab, 8)
        eng.fetch_outputs(handle)

    assert len(slab_ids) == 1  # same preallocated buffer every time
    assert eng.staging_stats()["slab_allocs_total"] == allocs_before


def test_exactly_one_host_to_device_transfer_per_batch(staging_engine, monkeypatch):
    """The packed dispatch path performs exactly ONE jax.device_put per
    batch, sourced from a pooled slab buffer — the acceptance criterion of
    the zero-copy staging redesign."""
    import tensorflow_web_deploy_tpu.serving.engine as engine_mod

    eng = staging_engine
    assert eng.cfg.packed_io
    puts = []
    real_put = engine_mod.jax.device_put

    def counting_put(x, *a, **kw):
        puts.append(x)
        return real_put(x, *a, **kw)

    monkeypatch.setattr(engine_mod.jax, "device_put", counting_put)

    slab = eng.acquire_staging(5, (128, 128, 3))
    rng = np.random.RandomState(2)
    for i in range(5):
        slab.write_row(i, rng.randint(0, 256, (128, 128, 3), np.uint8), (100, 90))
    handle = eng.dispatch_staged(slab, 5)
    eng.fetch_outputs(handle)

    assert len(puts) == 1
    assert puts[0] is slab.buf  # shipped straight from the staging buffer


def test_no_cross_batch_row_bleed(staging_engine):
    """A small batch after a full one must not inherit rows: results match
    per-image execution even though the slab still holds the previous
    batch's bytes in its padding rows."""
    eng = staging_engine
    rng = np.random.RandomState(3)
    full = rng.randint(0, 256, (8, 128, 128, 3), np.uint8)
    hws8 = np.full((8, 2), 128, np.int32)
    eng.run_batch(full, hws8)  # slab now full of this batch's bytes

    small = rng.randint(0, 256, (3, 128, 128, 3), np.uint8)
    hws3 = np.full((3, 2), 128, np.int32)
    scores, idx = eng.run_batch(small, hws3)
    assert scores.shape[0] == 3

    for i in range(3):
        s1, i1 = eng.run_batch(small[i : i + 1], hws3[i : i + 1])
        np.testing.assert_allclose(scores[i], s1[0], rtol=1e-5, atol=1e-6)
        np.testing.assert_array_equal(idx[i], i1[0])


def test_batcher_writes_rows_into_slab(staging_engine):
    """End to end through the batcher: the dispatcher row-stages into the
    engine's slab (no stacked intermediate), results route correctly, and
    /stats-visible occupancy reflects the padding."""
    eng = staging_engine
    b = Batcher(eng, max_batch=8, max_delay_ms=5.0)
    b.start()
    try:
        rng = np.random.RandomState(4)
        imgs = [rng.randint(0, 256, (128, 128, 3), np.uint8) for _ in range(6)]
        futures = [b.submit(img, (128, 128)) for img in imgs]
        rows = [f.result(timeout=60) for f in futures]
    finally:
        b.stop()
    assert len(rows) == 6
    snap = b.stats.snapshot()
    assert snap["requests_total"] == 6
    # occupancy: real rows / bucket rows, in (0, 1]
    assert snap["batch_occupancy"] is not None
    assert 0 < snap["batch_occupancy"] <= 1.0
    assert snap["batches_dispatched"] >= 1


def test_concurrent_acquire_never_blocks(staging_engine):
    """Pipelined callers may hold several slabs at once; acquisition
    allocates instead of blocking, and the pool cap bounds what is kept."""
    eng = staging_engine
    held = [eng.acquire_staging(8, (128, 128, 3)) for _ in range(10)]
    ids = {id(s.buf) for s in held}
    assert len(ids) == 10  # all distinct while held
    for s in held:
        eng._release_staging(s)
    pooled = eng.staging_stats()["slabs_pooled"]
    assert pooled <= eng._staging_cap


def _out(eng):
    st = eng.staging_stats()
    return st["slabs_out"], st["slabs_out_bytes"]


def test_staging_pool_byte_budget_evicts_lru(staging_engine):
    """Pooled (idle) slab memory is bounded by ``staging_pool_bytes`` plus
    the bytes that are out: a slab returned while another is out is kept;
    once nothing is out the pool trims to the floor, dropping slabs from
    the least-recently-used shape key, so warmup-only buckets give their
    memory back to the hot shapes."""
    eng = staging_engine
    saved = eng._staging_budget
    a = eng.acquire_staging(8, (128, 128, 3))
    b = eng.acquire_staging(8, (64, 64, 3))  # second shape key
    assert a.key != b.key
    try:
        eng._staging_budget = a.total_bytes  # floor: room for one big slab only
        eng._release_staging(a)  # b is still out: the budget is floor + b
        assert eng._staging_pool.get(a.key)
        stats = eng.staging_stats()
        assert stats["slabs_pooled_bytes"] <= eng._staging_budget + b.total_bytes
        eng._release_staging(b)  # nothing out: over the floor, a's key is LRU
        stats = eng.staging_stats()
        assert stats["slabs_out"] == 0 and stats["slabs_out_bytes"] == 0
        assert stats["slabs_pooled_bytes"] <= eng._staging_budget
        assert not eng._staging_pool.get(a.key)
        assert eng._staging_pool.get(b.key)
    finally:
        eng._staging_budget = saved


def test_staging_lru_eviction_order_multi_shape(staging_engine):
    """Three shape keys over budget: eviction walks strict LRU order (the
    key touched longest ago goes first), and a key re-touched by a fresh
    acquire stops being the victim. The budget at each return is the floor
    plus what is still out."""
    eng = staging_engine
    saved = eng._staging_budget
    a = eng.acquire_staging(8, (128, 128, 3))
    b = eng.acquire_staging(8, (96, 96, 3))
    c = eng.acquire_staging(8, (64, 64, 3))
    assert len({a.key, b.key, c.key}) == 3
    assert a.total_bytes > b.total_bytes + c.total_bytes
    try:
        # The floor fits exactly the two smaller slabs.
        eng._staging_budget = b.total_bytes + c.total_bytes
        eng._release_staging(a)  # b + c out: a fits beside the floor, kept
        assert eng._staging_pool.get(a.key)
        eng._release_staging(b)  # c out: a + b > floor + c → evict a (LRU)
        assert not eng._staging_pool.get(a.key)
        eng._release_staging(c)  # nothing out: b + c is the floor, both stay
        assert eng._staging_pool.get(b.key) and eng._staging_pool.get(c.key)
        assert eng.staging_stats()["slabs_pooled_bytes"] <= eng._staging_budget
        # Re-touching b (acquire) makes c the LRU among pooled keys.
        b2 = eng.acquire_staging(8, (96, 96, 3))
        eng._staging_budget = b2.total_bytes  # only room for one now
        eng._release_staging(b2)  # c must be evicted, not the fresh b
        assert eng._staging_pool.get(b2.key)
        assert not eng._staging_pool.get(c.key)
    finally:
        eng._staging_budget = saved


def test_lru_eviction_never_touches_inflight_slabs(staging_engine):
    """The byte budget bounds IDLE memory only: a slab held in flight (or
    by a lessee) is invisible to eviction — its bytes survive any pool
    churn byte-for-byte. While it is out the pool may hold that many bytes
    beside the floor (and so reuses a smaller slab that comes and goes);
    when it returns, the pool falls back to the floor."""
    eng = staging_engine
    saved = eng._staging_budget
    held = eng.acquire_staging(8, (128, 128, 3))  # in flight, not yet released
    rng = np.random.RandomState(7)
    payload = rng.randint(0, 256, (128, 128, 3), np.uint8)
    held.write_row(0, payload, (128, 128))
    try:
        eng._staging_budget = 1  # the floor keeps nothing by itself
        bufs = set()
        for _ in range(3):
            other = eng.acquire_staging(8, (64, 64, 3))
            bufs.add(id(other.buf))
            eng._release_staging(other)
            stats = eng.staging_stats()
            assert stats["slabs_out_bytes"] == held.total_bytes
            assert stats["slabs_pooled_bytes"] <= 1 + held.total_bytes
        assert len(bufs) == 1  # kept under held's bytes, and taken again
        # the in-flight slab was never pooled, evicted, or overwritten
        np.testing.assert_array_equal(held.canvases[0], payload)
        eng._release_staging(held)
        held = None
        stats = eng.staging_stats()
        assert stats["slabs_out"] == 0
        assert stats["slabs_pooled_bytes"] <= 1
    finally:
        eng._staging_budget = saved
        if held is not None:
            eng._release_staging(held)


def test_returned_slab_over_the_floor_is_kept_while_its_like_is_out(staging_engine):
    """The rule itself. With nothing else out, a returned slab larger than
    ``staging_pool_bytes`` is dropped and the next acquire allocates (every
    batch of a shape over the floor paid that before the budget followed
    the bytes out). With one slab of the shape held out, the returned one
    is kept, and the next acquire takes it: no allocation."""
    eng = staging_engine
    saved = eng._staging_budget
    shape = (128, 128, 3)
    assert _out(eng) == (0, 0)
    try:
        eng._staging_budget = 1024  # far under one slab of this shape
        eng.release_staging(eng.acquire_staging(8, shape))  # trims what earlier tests left
        # Nothing else out: allocations = acquisitions.
        s0 = eng.staging_stats()
        for _ in range(3):
            lone = eng.acquire_staging(8, shape)
            assert lone.total_bytes > eng._staging_budget
            eng.release_staging(lone)
            assert not eng._staging_pool.get(lone.key)
        s1 = eng.staging_stats()
        assert s1["slab_acquires_total"] - s0["slab_acquires_total"] == 3
        assert s1["slab_allocs_total"] - s0["slab_allocs_total"] == 3
        # One held out (a builder open, a batch in flight): reuse.
        held = eng.acquire_staging(8, shape)
        first = eng.acquire_staging(8, shape)
        s2 = eng.staging_stats()
        assert (s2["slabs_out"], s2["slabs_out_bytes"]) == (2, 2 * held.total_bytes)
        eng.release_staging(first)
        assert first in eng._staging_pool[first.key]
        for _ in range(3):
            again = eng.acquire_staging(8, shape)
            assert again is first
            eng.release_staging(again)
        s3 = eng.staging_stats()
        assert s3["slab_acquires_total"] - s2["slab_acquires_total"] == 3
        assert s3["slab_allocs_total"] == s2["slab_allocs_total"]
        assert s3["slabs_pooled_bytes"] <= eng._staging_budget + s3["slabs_out_bytes"]
        eng.release_staging(held)
    finally:
        eng._staging_budget = saved


def test_pool_trims_to_the_floor_when_the_last_slab_out_returns(staging_engine):
    """Idle bytes never exceed the floor plus the bytes out, at every
    return; when the last slab out comes back the pool is at the floor
    again, even where the per-key cap, not the budget, dropped that slab."""
    eng = staging_engine
    saved, saved_cap = eng._staging_budget, eng._staging_cap
    shape = (128, 128, 3)
    assert _out(eng) == (0, 0)
    try:
        eng._staging_budget = 1024
        held = [eng.acquire_staging(8, shape) for _ in range(5)]
        for slab in held[:-1]:
            eng.release_staging(slab)
            st = eng.staging_stats()
            assert st["slabs_pooled_bytes"] <= 1024 + st["slabs_out_bytes"]
        assert eng.staging_stats()["slabs_pooled"] >= 1  # traffic keeps its like
        eng.release_staging(held[-1])
        st = eng.staging_stats()
        assert (st["slabs_out"], st["slabs_out_bytes"]) == (0, 0)
        assert st["slabs_pooled_bytes"] <= 1024 and st["slabs_pooled"] == 0
        # The same at the per-key cap: the last return is dropped by the
        # cap and the trim still runs.
        eng._staging_cap = 2
        held = [eng.acquire_staging(8, shape) for _ in range(4)]
        for slab in held:
            eng.release_staging(slab)
            assert len(eng._staging_pool[slab.key]) <= 2
        st = eng.staging_stats()
        assert st["slabs_out"] == 0 and st["slabs_pooled_bytes"] <= 1024
    finally:
        eng._staging_budget, eng._staging_cap = saved, saved_cap


@pytest.mark.parametrize("path", ["release", "fetch", "failed_dispatch"])
def test_slabs_out_returns_to_zero(staging_engine, monkeypatch, path):
    """``slabs_out`` / ``slabs_out_bytes`` count a slab from acquire to its
    return, whichever way it comes back: released undispatched, fetched,
    or recycled by the batcher after a dispatch that raised."""
    eng = staging_engine
    assert _out(eng) == (0, 0)
    if path == "failed_dispatch":
        def boom(*a, **kw):
            raise RuntimeError("transient device error")

        monkeypatch.setattr(eng, "_dispatch_on", boom)
        inflight = eng.staging_stats()["dispatches_inflight"]
        b = Batcher(eng, max_batch=8, max_delay_ms=1.0)
        b.start()
        try:
            f = b.submit(np.zeros((128, 128, 3), np.uint8), (128, 128))
            with pytest.raises(RuntimeError):
                f.result(timeout=30)
        finally:
            b.stop()
        assert eng.staging_stats()["dispatches_inflight"] == inflight
    else:
        slab = eng.acquire_staging(8, (128, 128, 3))
        assert _out(eng) == (1, slab.total_bytes)
        if path == "release":
            eng.release_staging(slab)
        else:
            eng.fetch_outputs(eng.dispatch_staged(slab, 8))
    assert _out(eng) == (0, 0)


@pytest.mark.parametrize("ragged", [False, True])
def test_warmup_ends_with_the_pool_at_its_floor(request, ragged):
    """Warm-up touches every (canvas, batch) pair, several at once, and
    each slab here is larger than the floor: while they are out the pool
    may keep their like, and when the last comes back it holds no more
    than ``staging_pool_bytes``, as before the budget followed the bytes
    out."""
    mc = ModelConfig(
        name="small_cls", pb_path=request.getfixturevalue("small_cls_pb"),
        input_size=(96, 96), preprocess="inception", dtype="float32",
    )
    floor = 20_000  # under the smallest slab: 64 * 64 * 3 * 8
    cfg = ServerConfig(
        model=mc, canvas_buckets=(64, 128), batch_buckets=(8, 16),
        staging_pool_bytes=floor, ragged=ragged,
    )
    eng = InferenceEngine(cfg)
    try:
        assert eng.ragged is ragged
        eng.warmup()
        st = eng.staging_stats()
        assert st["slab_acquires_total"] >= 4 and st["slab_allocs_total"] >= 1
        assert (st["slabs_out"], st["slabs_out_bytes"]) == (0, 0)
        assert st["slabs_pooled_bytes"] <= floor
    finally:
        eng.close()


def test_slab_held_back_until_last_lease_drops(staging_engine):
    """The slot-lease pool contract: fetch completing does NOT return the
    slab while a lessee still holds a slot (it may be mid-decode into its
    row); the drop of the last lease does."""
    eng = staging_engine
    slab = eng.acquire_staging(8, (128, 128, 3))
    slab.add_lease()  # a worker leases a slot
    rng = np.random.RandomState(8)
    slab.write_row(0, rng.randint(0, 256, (128, 128, 3), np.uint8), (128, 128))
    handle = eng.dispatch_staged(slab, 1)
    eng.fetch_outputs(handle)  # fetch done, lease still out
    assert slab not in eng._staging_pool.get(slab.key, [])
    slab.drop_lease()  # lessee resolves → NOW pool-eligible
    assert slab in eng._staging_pool.get(slab.key, [])


def test_release_staging_recycles_undispatched_slab(staging_engine):
    """A slab acquired for a builder that sealed with only holes returns
    via release_staging — same lease hold-back as the fetch path."""
    eng = staging_engine
    slab = eng.acquire_staging(8, (128, 128, 3))
    slab.add_lease()
    eng.release_staging(slab)  # never dispatched; lessee still out
    assert slab not in eng._staging_pool.get(slab.key, [])
    slab.drop_lease()
    assert slab in eng._staging_pool.get(slab.key, [])


def test_jpeg_fast_path_single_copy_into_slab(staging_engine):
    """The tentpole acceptance criterion: on the JPEG fast path the wire
    bytes make exactly ONE host copy — libjpeg's decode write straight
    into the slab row the batch ships. Asserted on buffer identity: the
    leased row shares memory with the dispatched slab's wire buffer, and
    the decode's pixels are visible there without any further write."""
    import io

    from PIL import Image

    from tensorflow_web_deploy_tpu import native
    from tensorflow_web_deploy_tpu.utils.tracing import Span

    if not native.available():
        pytest.skip("no compiler/libjpeg for the native extension")
    eng = staging_engine
    rng = np.random.RandomState(9)
    buf = io.BytesIO()
    Image.fromarray(
        (rng.rand(100, 90, 3) * 255).astype(np.uint8)
    ).save(buf, "JPEG")
    data = buf.getvalue()

    b = Batcher(eng, max_batch=8, max_delay_ms=5.0)
    assert b.supports_lease
    b.start()
    try:
        plan = native.plan_decode(data, eng.cfg.canvas_buckets, eng.cfg.wire_format)
        assert plan is not None
        s, row_shape, orig = plan
        assert orig == (100, 90)
        span = Span("copy-count")
        lease = b.lease(row_shape, span=span)
        slab = lease.builder.slab
        # identity: the decode destination IS the slab's wire buffer
        assert lease.row.base is not None
        assert np.shares_memory(lease.row, slab.buf)
        hw = native.decode_into_row(data, lease.row, s, eng.cfg.wire_format)
        assert hw == (100, 90)
        # the decoded pixels are already in the wire buffer — no copy left
        assert slab.buf[lease.index, : 90 * 3].any()
        lease.commit(hw)
        scores, idx = lease.future.result(timeout=60)
        assert np.all(np.isfinite(scores))
        assert "lease_wait" in span.stages and "queue_wait" in span.stages
    finally:
        b.stop()
