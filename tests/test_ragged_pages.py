"""Ragged arenas shipped to the device by pages while their batch is open
(serving/engine.py: ``RaggedSlab.settle``, ``PageShipper``,
``dispatch_ragged``; serving/batcher.py: ``_settle_locked``).

A page is handed to its replica's shipper once every byte of it is
allocated and every slot over it is settled (committed, released or
force-expired); the launch takes the pages that landed and copies the rest.
What the unpack makes of the pages must be, bit for bit, what it makes of
the prefix copied whole, each byte of the prefix must cross once, and a
replica of several devices keeps the one copy at launch. The served
program is replaced by one that answers the unpacked canvases and sizes
themselves, so that the batch's answer IS what the unpack made.
"""

import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tensorflow_web_deploy_tpu.ops.image import unpack_ragged
from tensorflow_web_deploy_tpu.serving import engine as engine_mod
from tensorflow_web_deploy_tpu.serving.batcher import _PENDING, Batcher
from tensorflow_web_deploy_tpu.serving.engine import (PAGE_BYTES, InferenceEngine,
                                                      RaggedSlab, page_sizes)
from tensorflow_web_deploy_tpu.utils.config import ModelConfig, ServerConfig


def _engine(canvases, buckets, placement="replicas=8"):
    """A tiny ragged engine whose serve program answers (canvases, hws)."""
    cfg = ServerConfig(
        model=ModelConfig(name="mobilenet_v2", source="native", task="classify",
                          zoo_width=0.25, zoo_classes=12, input_size=(48, 48),
                          preprocess="inception", topk=3, placement=placement),
        canvas_buckets=tuple(canvases), batch_buckets=tuple(buckets),
        max_batch=max(buckets), ragged=True, warmup=False, wire_format="rgb",
    )
    eng = InferenceEngine(cfg)
    eng._serve_exe_for = lambda rep, key, bucket: (lambda params, c, h: (c, h))
    return eng


@pytest.fixture(scope="module")
def paged():
    eng = _engine((1024, 2048, 4096), (1, 2, 4))
    assert eng._paged  # one device a replica
    yield eng
    eng.close()


class _Puts:
    """Every ``jax.device_put`` of bytes of ``slab``'s arena: (offset,
    length, thread name)."""

    def __init__(self, monkeypatch, slab):
        self.base = slab.buf.ctypes.data
        self.end = self.base + slab.buf.nbytes
        self.ranges: list[tuple[int, int, str]] = []
        lock = threading.Lock()
        real = jax.device_put

        def put(x, *args, **kw):
            for leaf in (x if isinstance(x, (list, tuple)) else [x]):
                if isinstance(leaf, np.ndarray):
                    at = leaf.__array_interface__["data"][0]
                    if self.base <= at < self.end:
                        with lock:
                            self.ranges.append((at - self.base, leaf.nbytes,
                                                threading.current_thread().name))
            return real(x, *args, **kw)

        monkeypatch.setattr(jax, "device_put", put)

    def tile(self) -> list[tuple[int, int]]:
        return sorted((a, n) for a, n, _ in self.ranges)


def _sizes(rng, s: int, slots: int, rows: int) -> list[tuple[int, int]]:
    """``slots`` image sizes that fit a canvas of ``s`` and whose bytes
    together end in the arena's canvas row ``rows``."""
    row = s * s * 3
    for _ in range(10_000):
        want = rng.uniform((rows - 1) * row + 1, rows * row)
        parts = rng.dirichlet(np.ones(slots)) * want / 3
        if parts.max() > s * s:
            continue
        hws = []
        for px in parts:
            h = int(rng.randint(max(1, int(np.ceil(px / s))), s + 1))
            hws.append((h, int(np.clip(round(px / h), 1, s))))
        total = sum(h * w * 3 for h, w in hws)
        if (rows - 1) * row < total <= rows * row:
            return hws
    raise AssertionError("no sizes found")


def _drain(slab, timeout=10.0):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        with slab._lease_lock:
            if not {engine_mod._PAGE_QUEUED, engine_mod._PAGE_SHIPPING} & set(slab._page_state):
                return
        time.sleep(0.002)
    raise AssertionError("the shipper did not drain")


# Every (canvas, dispatch bucket, shipped rows) the fixture's buckets give
# at canvas 1024 and 2048; canvas 4096 up to 2 rows (96 MiB a slab here).
VARIANTS = ([(1024, b, r) for b in (1, 2, 4) for r in range(1, b + 1)]
            + [(s, b, r) for s in (2048, 4096) for b in (1, 2) for r in range(1, b + 1)])


@pytest.mark.parametrize("s,bucket,rows", VARIANTS)
def test_early_pages_unpack_bit_for_bit_like_the_whole_prefix(paged, monkeypatch, s, bucket, rows):
    """Random sizes, a random settling order with holes and force-expired
    slots (bytes half written), the launch before or after the shipper has
    drained: the canvases and hws equal, bit for bit, those the unpack makes
    of the prefix copied whole; each prefix byte crosses exactly once; no
    page is handed over before every slot over it is settled; h2d_bytes is
    the prefix and the meta table, and the page counts add up."""
    rng = np.random.RandomState(s + 10 * bucket + rows)
    slab = paged.acquire_ragged(bucket, s)
    assert slab.paged and slab.bucket == bucket
    puts = _Puts(monkeypatch, slab)
    hws = _sizes(rng, s, bucket, rows)
    fates = list(rng.choice(["commit", "release", "expire"], bucket - 1, p=[0.6, 0.2, 0.2]))
    fates.append("commit")  # the last slot rides, so the batch is `bucket` rows
    views = [slab.alloc(h * w * 3)[1] for h, w in hws]
    settled, handed = set(), []
    for i in rng.permutation(bucket):
        h, w = hws[i]
        if fates[i] == "commit":
            views[i][:] = rng.randint(0, 256, views[i].size)
            slab.write_hw(i, (h, w))
        elif fates[i] == "expire":  # the lessee was half way through its decode
            views[i][: views[i].size // 2] = rng.randint(0, 256, views[i].size // 2)
        settled.add(int(i))
        pages = slab.settle(i)
        for p in pages:
            assert (p + 1) * PAGE_BYTES <= slab.used
            over = [j for j in range(slab.slots)
                    if slab.meta[j, 0] < (p + 1) * PAGE_BYTES and slab._ends[j] > p * PAGE_BYTES]
            assert set(over) <= settled, (p, over, settled)
        handed += pages
        if pages:
            paged.ship_pages(slab, pages, seq=1)
    assert len(set(handed)) == len(handed)  # each page once
    drained = rng.rand() < 0.5
    if drained:
        _drain(slab)
    for i, fate in enumerate(fates):
        if fate != "commit":
            slab.write_hw(i, (1, 1))  # the launch's hole padding
    assert slab.rows_shipped(bucket) == rows
    prefix = rows * s * s * 3
    want = jax.jit(lambda a, m: unpack_ragged(a, m, s))(slab.buf[:prefix].copy(), slab.meta.copy())
    rec = {"seq": 1, "rows": bucket, "t_launch": time.monotonic()}
    got = paged.fetch_outputs(paged.dispatch_ragged(slab, bucket, rec=rec), rec=rec)
    np.testing.assert_array_equal(got[0], np.asarray(want[0]))
    np.testing.assert_array_equal(got[1], np.asarray(want[1]))

    tile, at = puts.tile(), 0
    for a, n in tile:
        assert a == at, tile
        at += n
    assert at == prefix
    early = [(a, n) for a, n, who in puts.ranges if who.startswith("page-ship-")]
    assert all(a % PAGE_BYTES == 0 and n == PAGE_BYTES for a, n in early)
    # The launch takes over the pages the shipper had not begun.
    assert {a // PAGE_BYTES for a, _ in early} <= set(handed)
    assert rec["h2d_bytes"] == prefix + slab.meta.nbytes
    assert rec["h2d_pages"] == len(page_sizes(prefix))
    assert rec["h2d_pages_early"] <= len(early)
    if drained:  # every handed page was put, before t_launch
        assert sorted(a // PAGE_BYTES for a, _ in early) == sorted(handed)
        assert rec["h2d_pages_early"] == len(handed)
    assert rec["h2d_early_bytes"] == rec["h2d_pages_early"] * PAGE_BYTES
    assert all(d is None for d in slab._pages_d)  # the pages went with the batch


def test_the_photos_cell_batch_ships_most_of_its_prefix_early(paged):
    """A canvas-4096 batch of two committed photos of 3 and 12 MP: every
    whole page below the last image's end goes before the launch."""
    slab = paged.acquire_ragged(2, 4096)
    for i, (h, w) in enumerate([(1536, 2048), (3000, 4000)]):
        _, view = slab.alloc(h * w * 3)
        view[:] = 7
        slab.write_hw(i, (h, w))
        pages = slab.settle(i)
        if pages:
            paged.ship_pages(slab, pages)
    _drain(slab)
    rec = {"seq": 2, "rows": 2, "t_launch": time.monotonic()}
    paged.fetch_outputs(paged.dispatch_ragged(slab, 2, rec=rec), rec=rec)
    used = (1536 * 2048 + 3000 * 4000) * 3
    assert rec["h2d_pages_early"] == used // PAGE_BYTES == 5
    assert rec["h2d_pages"] == 6  # one canvas row of 48 MiB
    assert rec["h2d_early_bytes"] / rec["h2d_bytes"] > 0.8


def test_a_released_arena_drops_its_pages_and_a_reused_one_ignores_stale_ones(paged):
    """Pages put for a batch that is discarded go with the release; a page
    handed over in an earlier cycle of a pooled arena is not put."""
    slab = paged.acquire_ragged(1, 4096)
    slab.alloc(4096 * 4096 * 3)
    slab.write_hw(0, (4096, 4096))
    pages = slab.settle(0)
    assert pages == list(range(6))  # a 48 MiB canvas row, whole pages
    gen = slab._gen
    paged.ship_pages(slab, pages)
    _drain(slab)
    assert sum(d is not None for d in slab._pages_d) == 6
    paged.release_staging(slab)
    assert all(d is None for d in slab._pages_d)
    assert slab.claim(gen, [0]) == []  # closed: nothing more goes
    again = paged.acquire_ragged(1, 4096)
    assert again is slab and slab._gen == gen + 1
    assert slab.claim(gen, [0]) == [] and not slab._closed
    paged.release_staging(again)


def test_a_replica_of_several_devices_copies_the_prefix_at_launch(monkeypatch):
    """Placement over all eight devices: no pages, the batcher hands none
    over, and the launch puts the prefix in one copy."""
    eng = _engine((1024,), (8,), placement=None)
    try:
        assert not eng._paged
        handed = []
        monkeypatch.setattr(eng, "ship_pages", lambda *a, **k: handed.append(a))
        b = Batcher(eng, max_batch=8, max_delay_ms=50, adaptive_delay=False)
        b.start()
        try:
            rng = np.random.RandomState(5)
            imgs = [rng.randint(0, 256, (900, 1000, 3)).astype(np.uint8) for _ in range(2)]
            puts = None
            futs = []
            for im in imgs:
                lease = b.lease_ragged(im.size, 1024)
                if puts is None:
                    puts = _Puts(monkeypatch, lease.builder.slab)
                    assert not lease.builder.slab.paged
                lease.row[:] = im.reshape(-1)
                futs.append(lease.commit(im.shape[:2]))
            rows = [f.result(timeout=60) for f in futs]
        finally:
            b.stop()
        for im, (canvas, hw) in zip(imgs, rows):
            np.testing.assert_array_equal(canvas[:900, :1000], im)
            assert tuple(hw) == (900, 1000)
        assert handed == []
        assert puts.tile() == [(0, 2 * 1024 * 1024 * 3)]
        life = b.lifecycle_stats()
        assert life["h2d_pages_total"] == life["h2d_pages_early_total"] == 0
        assert life["h2d_early_bytes_total"] == 0
    finally:
        eng.close()


def test_through_the_batcher_pages_go_before_the_launch_and_the_counters_add_up(paged, monkeypatch):
    """Leases committed in a random order, one released, one left to expire:
    every committed photo answers its own padded canvas; each page was
    handed over with every lease over it settled and before its batch's
    ``t_launch``, under the seq the batch then got; the lifecycle's page
    and byte counters add up to the batches' records."""
    handed = []
    real = paged.ship_pages

    def ship(slab, pages, seq=None):
        b = next(x for x in [*batcher._open.values(), *batcher._closing] if x.slab is slab)
        for p in pages:
            lo, hi = p * PAGE_BYTES, (p + 1) * PAGE_BYTES
            for lease in b.leases:
                if slab.meta[lease.index, 0] < hi and slab._ends[lease.index] > lo:
                    assert lease.state != _PENDING, (p, lease.index)
        assert not b.dispatched
        handed.append((seq, tuple(pages), time.monotonic()))
        real(slab, pages, seq)

    monkeypatch.setattr(paged, "ship_pages", ship)
    batcher = Batcher(paged, max_batch=4, max_delay_ms=300, adaptive_delay=False,
                      lease_timeout_s=0.2)
    batcher.start()
    try:
        rng = np.random.RandomState(11)
        before = batcher.lifecycle_stats()
        for _ in range(2):
            leases, imgs = [], []
            for _ in range(4):
                h, w = int(rng.randint(1200, 2049)), int(rng.randint(1200, 2049))
                imgs.append(rng.randint(0, 256, (h, w, 3)).astype(np.uint8))
                leases.append(batcher.lease_ragged(h * w * 3, 2048))
            fates = list(rng.permutation(["commit", "commit", "release", "expire"]))
            order = rng.permutation(4)
            futs = {}
            for i in order:
                lease, im = leases[i], imgs[i]
                if fates[i] == "commit":
                    lease.row[:] = im.reshape(-1)
                    futs[i] = lease.commit(im.shape[:2])
                elif fates[i] == "release":
                    lease.release()
            for i, f in futs.items():
                canvas, hw = f.result(timeout=60)
                h, w = imgs[i].shape[:2]
                np.testing.assert_array_equal(canvas[:h, :w], imgs[i])
                assert not canvas[h:].any() and not canvas[:, w:].any()
                assert tuple(hw) == (h, w)
            leases[fates.index("expire")].release()  # the expired lessee gives up at last
        deadline = time.monotonic() + 5
        while batcher.inflight_batches and time.monotonic() < deadline:
            time.sleep(0.01)
        after = batcher.lifecycle_stats()
        recs = {r["seq"]: r for r in batcher.batch_timeline()}
    finally:
        batcher.stop()
    assert handed
    for seq, _, t in handed:
        assert seq in recs and t < recs[seq]["t_launch"]
    mine = [r for r in recs.values() if r["h2d_pages"]]
    assert len(mine) == 2

    def delta(k):
        return after[k] - before[k]

    assert delta("h2d_pages_total") == sum(r["h2d_pages"] for r in mine)
    assert delta("h2d_pages_early_total") == sum(r["h2d_pages_early"] for r in mine)
    assert delta("h2d_early_bytes_total") == delta("h2d_pages_early_total") * PAGE_BYTES
    assert 0 < delta("h2d_pages_early_total") <= sum(len(p) for _, p, _ in handed)
    assert delta("h2d_bytes_total") == sum(r["h2d_bytes"] for r in mine)
    assert delta("h2d_early_bytes_total") < delta("h2d_bytes_total")


def test_word_pages_through_the_kernel_match_the_gather(monkeypatch):
    """The Mosaic kernel's arena as uint32 pages (through the Pallas
    interpreter), with pages small enough that images straddle them: the
    same canvases as the XLA gather over the prefix copied whole."""
    from functools import partial

    from tensorflow_web_deploy_tpu.ops import image

    monkeypatch.setattr(engine_mod, "PAGE_BYTES", 256 << 10)
    monkeypatch.setattr(image, "unpack_kernel_applies", lambda s, n: s == 512 and n == 1)
    monkeypatch.setattr(image, "unpack_ragged", partial(image.unpack_ragged, interpret=True))
    eng = _engine((512,), (2,))
    try:
        rng = np.random.RandomState(17)
        slab = eng.acquire_ragged(2, 512)
        assert len(slab._page_state) == 6  # 1.5 MiB in pages of 256 KiB
        for i, (h, w) in enumerate([(333, 411), (101, 57)]):
            _, view = slab.alloc(h * w * 3)
            view[:] = rng.randint(0, 256, view.size)
            slab.write_hw(i, (h, w))
            pages = slab.settle(i)
            if pages:
                eng.ship_pages(slab, pages)
        _drain(slab)
        assert slab._page_state.count(engine_mod._PAGE_SHIPPED) == 1
        rows = slab.rows_shipped(2)
        prefix = rows * 512 * 512 * 3
        want = jax.jit(lambda a, m: unpack_ragged(a, m, 512))(slab.buf[:prefix].copy(), slab.meta.copy())
        rec = {"seq": 3, "rows": 2, "t_launch": time.monotonic()}
        got = eng.fetch_outputs(eng.dispatch_ragged(slab, 2, rec=rec), rec=rec)
        assert rec["unpack_kernel"] and rec["h2d_pages_early"] == 1
        assert rec["h2d_pages"] == len(page_sizes(prefix)) == -(-prefix // (256 << 10))
        assert bool(jnp.array_equal(got[0], want[0])) and bool(jnp.array_equal(got[1], want[1]))
    finally:
        eng.close()


def test_an_unpaged_slab_settles_to_nothing():
    """The classic test fakes' arenas (and a multi-device engine's) keep no
    page table: settling hands nothing over."""
    slab = RaggedSlab(64, 4)
    i, _ = slab.alloc(64 * 64 * 3)
    slab.write_hw(i, (64, 64))
    assert not slab.paged and slab.settle(i) == [] and slab._page_state == []
