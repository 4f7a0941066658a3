"""serving/staging.py: the one way an image becomes a batch slot.

Every wire (ragged arenas, classic rgb rows, classic I420 rows, a batcher
without slot leases) takes every source (a JPEG the native decoder takes,
a PNG through PIL, a JPEG whose header parses and whose body the native
decoder refuses) past every cache outcome through ``stage_image``, and
every way out by an exception leaves nothing behind. Real batchers over
real slabs; the engines run no model.
"""

import io
import time
from types import SimpleNamespace

import numpy as np
import pytest
from PIL import Image

from tensorflow_web_deploy_tpu import native
from tensorflow_web_deploy_tpu.serving.batcher import (
    BacklogFull, Batcher, ShuttingDown,
)
from tensorflow_web_deploy_tpu.serving.engine import RaggedSlab, StagingSlab
from tensorflow_web_deploy_tpu.serving.overload import Degraded
from tensorflow_web_deploy_tpu.serving.respcache import ResponseCache
from tensorflow_web_deploy_tpu.serving.staging import (
    UndecodableImage, abort_slots, stage_image,
)
from tensorflow_web_deploy_tpu.utils.tracing import Span

CANVAS = 64
BUCKETS = (CANVAS,)
HW = (40, 56)  # every source decodes to this


class SlabEngine:
    """The staging API over real slabs; a row's answer is h + w of what
    was committed into it, and ``rows`` counts the real (not hole) rows
    that reached a dispatch."""

    supports_slot_lease = True
    max_batch = 4

    def __init__(self, wire="rgb", ragged=False):
        self.cfg = SimpleNamespace(wire_format=wire, canvas_buckets=BUCKETS)
        self.ragged = ragged
        self.rows = 0

    def acquire_staging(self, n, row_shape):
        slab = StagingSlab(tuple(row_shape), max(n, 4), packed=False)
        slab.arm(lambda _slab: None)
        return slab

    def acquire_ragged(self, n, canvas_s):
        slab = RaggedSlab(canvas_s, max(n, 4))
        slab.arm(lambda _slab: None)
        return slab

    def release_staging(self, slab):
        slab.finish_fetch()

    def dispatch_staged(self, slab, n):
        return slab, slab.hws[:n].copy()

    def dispatch_ragged(self, slab, n, spans=None):
        return slab, slab.meta[:n, 1:3].copy()

    def fetch_outputs(self, handle):
        slab, hws = handle
        slab.finish_fetch()
        self.rows += int((hws.prod(axis=1) > 1).sum())
        return (hws.sum(axis=1),)


class PlainEngine:
    """No staging API at all: ``prepare_bytes`` and ``batcher.submit``."""

    max_batch = 4
    cfg = SimpleNamespace(wire_format="rgb", canvas_buckets=BUCKETS)

    def __init__(self):
        self.rows = 0

    def prepare_bytes(self, data):
        return native.decode_to_canvas(data, BUCKETS, "rgb")

    def dispatch_batch(self, canvases, hws):
        return np.asarray(hws)

    def fetch_outputs(self, hws):
        self.rows += len(hws)
        return (hws.sum(axis=1),)


WIRES = {
    "ragged": lambda: SlabEngine(ragged=True),
    "rgb": lambda: SlabEngine("rgb"),
    "yuv420": lambda: SlabEngine("yuv420"),
    "no_leases": PlainEngine,
}
# What a lease of this wire reserves for a 40 x 56 image.
NEED = {"ragged": ("lease_ragged", HW[0] * HW[1] * 3),
        "rgb": ("lease", (CANVAS, CANVAS, 3)),
        "yuv420": ("lease", (CANVAS * 3 // 2, CANVAS))}


def _source(kind, seed=7):
    buf = io.BytesIO()
    img = Image.fromarray(
        (np.random.RandomState(seed).rand(*HW, 3) * 255).astype(np.uint8))
    if kind == "jpeg":
        img.save(buf, "JPEG", quality=90)
    elif kind == "png":
        img.save(buf, "PNG")
    elif kind == "cmyk_jpeg":
        # libjpeg reads its header; decode.c refuses the colour space
        # after it, PIL converts it.
        img.convert("CMYK").save(buf, "JPEG", quality=90)
    else:  # a header that parses and a body nobody decodes
        img.save(buf, "JPEG", quality=90)
        return buf.getvalue()[:200]
    return buf.getvalue()


class Rig:
    """One started batcher of a wire, with its lease entry points counted."""

    def __init__(self, wire, monkeypatch, **batcher_kw):
        self.engine = WIRES[wire]()
        self.batcher = Batcher(self.engine, max_batch=4, max_delay_ms=2.0,
                               adaptive_delay=False, bulk_max_delay_ms=2.0,
                               **batcher_kw)
        self.mv = SimpleNamespace(name="m", version=1, engine=self.engine,
                                  model_cfg=SimpleNamespace(dtype="float32"))
        self.calls = []
        for name in ("lease", "lease_ragged", "submit"):
            monkeypatch.setattr(self.batcher, name, self._counted(name))
        self.batcher.start()

    def _counted(self, name):
        real = getattr(self.batcher, name)

        def call(first, *a, **kw):
            self.calls.append((name, first if name != "submit" else None))
            return real(first, *a, **kw)

        return call

    def stage(self, data, cache, **kw):
        span = Span()
        slot = stage_image(data, batcher=self.batcher, mv=self.mv,
                           cache=cache, topk=3, buckets=BUCKETS, span=span,
                           **kw)
        return slot, span.stages_copy()

    def holes(self):
        return self.batcher.builder_stats()["holes_total"]

    def pending(self):
        bs = self.batcher.builder_stats()
        return bs["leased_slots"] + bs["bulk"]["leased_slots"]


@pytest.fixture
def rig(monkeypatch):
    made = []

    def make(wire, **kw):
        made.append(Rig(wire, monkeypatch, **kw))
        return made[-1]

    yield make
    for r in made:
        r.batcher.stop()


def _settle(rig, n_rows, timeout=5.0):
    """Wait until the engine has seen ``n_rows`` real rows and no slot is
    left leased."""
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if rig.engine.rows >= n_rows and rig.pending() == 0:
            break
        time.sleep(0.005)
    time.sleep(0.02)  # a row too many would have come by now
    assert rig.engine.rows == n_rows
    assert rig.pending() == 0


@pytest.mark.parametrize("cache_case", ["disabled", "miss", "hit", "wait"])
@pytest.mark.parametrize("source", ["jpeg", "png", "cmyk_jpeg"])
@pytest.mark.parametrize("wire", list(WIRES))
def test_every_wire_source_and_cache_outcome(rig, wire, source, cache_case):
    r = rig(wire)
    data = _source(source)
    cache = None if cache_case == "disabled" else ResponseCache(1 << 20)
    leases = wire != "no_leases"
    # Which sources lease BEFORE the lookup (a hit then leaves a hole), and
    # which are given back because the native decoder refused the body.
    native_first = leases and native.available() and source != "png"
    refused = native_first and source == "cmyk_jpeg"

    # A job computes (hit) or is still computing (wait) the same bytes:
    # the interactive lookup below finds the bulk key, or it would miss.
    led = None
    rows = 0
    if cache_case in ("hit", "wait"):
        led, _ = r.stage(data, cache, bulk=True)
        assert led[0] == "own" and led[3] is not None
        r.batcher.flush_bulk()
        led[1].result(timeout=5)
        rows = 1
        if cache_case == "hit":
            etag = cache.complete(led[3], {"answer": 42})
    holes0, calls0 = r.holes(), len(r.calls)

    slot, stages = r.stage(data, cache)

    answered = cache_case in ("hit", "wait")
    assert slot[0] == {"disabled": "own", "miss": "own", "hit": "done",
                       "wait": "wait"}[cache_case]
    if cache_case == "hit":
        assert slot == ("done", {"answer": 42}, etag)
    elif cache_case == "wait":
        assert slot[1] is led[3]
        etag = cache.complete(led[3], {"answer": 42})
        assert slot[1].future.result(timeout=1) == ({"answer": 42}, etag)
    else:
        _, future, orig, flight, lease = slot
        assert orig == HW
        assert (flight is None) == (cache is None)
        assert (lease is None) == (not leases)
        assert future.result(timeout=5)[0] == sum(HW)
        rows += 1
        if flight is not None:
            cache.abort(flight, RuntimeError("test over"))

    # The entry points taken, in order: a native decode leases before the
    # lookup; PIL leases (or submits) only a miss.
    calls = r.calls[calls0:]
    if not leases:  # submit leases for itself: count the way in only
        assert [c for c in calls if c[0] == "submit"] == (
            [] if answered else [("submit", None)])
    else:
        want = []
        if native_first:
            want.append(NEED[wire])
        if (refused or not native_first) and not answered:
            want.append(NEED[wire])
        assert calls == want
    # A lease given back is a hole: the refused native decode's, and the
    # row a hit or a wait had decoded into.
    assert r.holes() - holes0 == int(refused) + int(
        answered and native_first and not refused)
    assert stages["image_decode"] > 0
    assert ("cache_lookup" in stages) == (cache is not None)
    _settle(r, rows)
    if cache is not None:
        assert cache.stats()["inflight"] == 0


@pytest.mark.parametrize("source", ["jpeg", "png"])
@pytest.mark.parametrize("wire", ["ragged", "rgb"])
@pytest.mark.parametrize("way", [
    "shutting_down_from_lease", "shutting_down_from_commit", "backlog_full",
    "degraded", "undecodable", "chaos"])
def test_every_way_out_leaves_nothing_behind(rig, monkeypatch, wire, source,
                                             way):
    r = rig(wire, **({"max_queue": 1} if way == "backlog_full" else {}))
    cache = ResponseCache(1 << 20)
    data = _source("truncated" if way == "undecodable" else source)
    kw, held, raises = {}, None, ShuttingDown
    if way == "shutting_down_from_lease":
        r.batcher.stop()
    elif way == "shutting_down_from_commit":
        def down(lease, hw, canvas=None):
            raise ShuttingDown("draining under a hot-swap")
        monkeypatch.setattr(r.batcher, "_commit", down)
    elif way == "backlog_full":
        name, need = NEED[wire]
        args = (need, CANVAS) if wire == "ragged" else (need,)
        held = getattr(r.batcher, name)(*args)
        raises = BacklogFull
    elif way == "degraded":
        kw, raises = {"shed_misses": True}, Degraded
    elif way == "undecodable":
        raises = UndecodableImage
    else:
        kw = {"chaos": SimpleNamespace(decode_fault=lambda: True)}
        raises = UndecodableImage

    with pytest.raises(raises) as err:
        r.stage(data, cache, **kw)

    if way == "chaos":
        assert str(err.value) == ("could not decode image "
                                  "(chaos: injected decode failure)")
        assert r.calls == []
    elif way == "undecodable":
        assert str(err.value) == "could not decode image" and not err.value.note
    if held is not None:
        held.release()
    assert r.pending() == 0, "a PENDING slot holds its builder back"
    assert cache.stats()["inflight"] == 0, "a led flight was leaked"
    # Nothing of this image is left to ship either.
    if way != "shutting_down_from_lease":
        _settle(r, 0)


def test_abort_slots_unwinds_own_slots_and_skips_the_rest(rig):
    r = rig("ragged")
    cache = ResponseCache(1 << 20)
    r.batcher.stop()  # nothing seals: the committed rows stay to unwind
    r.batcher._running = True
    own = [r.stage(_source("jpeg", seed), cache)[0] for seed in (1, 2)]
    r.batcher._running = False
    assert [s[0] for s in own] == ["own", "own"] and r.pending() == 2
    exc = RuntimeError("a sibling upload was refused")
    abort_slots([("done", {}, "etag"), ("wait", object()), *own], cache, exc)
    assert r.pending() == 0 and cache.stats()["inflight"] == 0
    for s in own:
        with pytest.raises(RuntimeError, match="sibling"):
            s[3].future.result(timeout=1)
    abort_slots(own, None, exc)  # again, and with no cache: harmless
