"""serving/staging.py: the one way an image becomes a batch slot.

Every wire (ragged arenas, classic rgb rows, classic I420 rows, a batcher
without slot leases) takes every source (a JPEG the native decoder takes,
a PNG through PIL, a JPEG whose header parses and whose body the native
decoder refuses) past every cache outcome through ``stage_image``, and
every way out by an exception leaves nothing behind. The cache is asked
first and keyed by the upload's bytes: a hit or a wait takes no lease and
calls no decoder, and whatever else decides the device's pixels (bucket
set, wire, topk, dtype, version) leads a flight of its own. Real batchers
over real slabs; the engines run no model.
"""

import io
import time
from types import SimpleNamespace

import numpy as np
import pytest
from PIL import Image

from tensorflow_web_deploy_tpu import native
from tensorflow_web_deploy_tpu.ops.image import decode_image
from tensorflow_web_deploy_tpu.serving.batcher import (
    BacklogFull, Batcher, DeadlineExceeded, QuotaExceeded, ShuttingDown,
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
        self.prepared = 0

    def prepare_bytes(self, data):
        self.prepared += 1
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


def _rewrapped(data, comment=b"re-saved by a gallery app"):
    """The same JPEG with a comment segment behind its SOI: other bytes,
    the same decoded pixels."""
    assert data[:2] == b"\xff\xd8"
    seg = b"\xff\xfe" + (len(comment) + 2).to_bytes(2, "big") + comment
    return data[:2] + seg + data[2:]


class CountingSpan(Span):
    """A Span that also counts its stamps by stage."""

    __slots__ = ("stamps",)

    def __init__(self):
        super().__init__()
        self.stamps = {}

    def add(self, stage, dur_s):
        self.stamps[stage] = self.stamps.get(stage, 0) + 1
        super().add(stage, dur_s)


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

    def stage(self, data, cache, *, topk=3, buckets=BUCKETS, mv=None, **kw):
        self.span = span = CountingSpan()
        slot = stage_image(data, batcher=self.batcher, mv=mv or self.mv,
                           cache=cache, topk=topk, buckets=buckets, span=span,
                           **kw)
        return slot, span.stages_copy()

    def decodes(self):
        """Images a decoder has served: ``prepare_bytes`` by the engine's
        count, the native decoder and PIL by the process's own."""
        if hasattr(self.engine, "prepared"):
            return self.engine.prepared
        st = native.stats()
        return st["native_decodes_total"] + st["pil_decodes_total"]

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
    # Which sources decode into a lease, and which of those leases are
    # given back because the native decoder refused the body.
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
    holes0, calls0, decodes0 = r.holes(), len(r.calls), r.decodes()

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
            # One lookup and one flight, also where the native decoder
            # refused the body and PIL took over.
            assert r.span.stamps["cache_lookup"] == 1
            assert cache.stats()["misses_total"] == 1
            assert cache.stats()["inflight"] == 1
            cache.abort(flight, RuntimeError("test over"))

    calls = r.calls[calls0:]
    if answered:
        # The lookup came first: no lease, no submit, no header probe, no
        # decoder, and so no hole either.
        assert calls == []
        assert r.decodes() == decodes0
        assert "image_decode" not in stages
    elif not leases:  # submit leases for itself: count the way in only
        assert [c for c in calls if c[0] == "submit"] == [("submit", None)]
    else:
        # A refused native decode gives its lease back, and PIL's pixels
        # take another.
        assert calls == [NEED[wire]] * (2 if refused else 1)
    if not answered:
        assert r.decodes() == decodes0 + 1
        assert stages["image_decode"] > 0
    # The one lease given back, so the one hole: the refused decode's.
    assert r.holes() - holes0 == int(refused and not answered)
    assert ("cache_lookup" in stages) == (cache is not None)
    _settle(r, rows)
    if cache is not None:
        st = cache.stats()
        assert st["inflight"] == 0
        assert st["digest_bytes_total"] == len(data) * (1 + answered)


# What else decides the pixels the device sees for the same bytes, or the
# answer made of them: each is part of the key.
OTHERWISE = {
    "bucket_set": dict(buckets=(CANVAS, 2 * CANVAS)),
    "topk": dict(topk=5),
    "dtype": dict(mv=dict(model_cfg=SimpleNamespace(dtype="int8"))),
    "version": dict(mv=dict(version=2)),
    "model": dict(mv=dict(name="another")),
}


@pytest.mark.parametrize("what", [*OTHERWISE, "wire"])
@pytest.mark.parametrize("wire", list(WIRES))
def test_the_same_bytes_otherwise_lead_a_flight_of_their_own(rig, wire, what):
    r = rig(wire)
    cache = ResponseCache(1 << 20)
    data = _source("jpeg")
    first, _ = r.stage(data, cache)
    assert first[0] == "own"
    if what == "wire":
        other = rig({"ragged": "rgb", "rgb": "yuv420", "yuv420": "no_leases",
                     "no_leases": "ragged"}[wire])
        second, _ = other.stage(data, cache)
    else:
        kw = dict(OTHERWISE[what])
        if "mv" in kw:
            kw["mv"] = SimpleNamespace(**{**vars(r.mv), **kw["mv"]})
        second, _ = r.stage(data, cache, **kw)
    assert second[0] == "own" and second[3] is not first[3]
    assert second[3].key != first[3].key
    st = cache.stats()
    assert st["inflight"] == 2 and st["misses_total"] == 2
    assert st["coalesced_total"] == 0
    # Nothing else changed: the same request again coalesces.
    again, _ = r.stage(data, cache)
    assert again == ("wait", first[3])
    abort_slots([first, second], cache, RuntimeError("test over"))


@pytest.mark.parametrize("other", ["one_byte", "trailing_byte",
                                   "metadata_added"])
@pytest.mark.parametrize("wire", list(WIRES))
def test_the_bytes_are_the_key_not_the_pixels(rig, wire, other):
    """The stated design: uploads whose bytes differ are two entries, also
    where their decoded pixels are the same."""
    r = rig(wire)
    cache = ResponseCache(1 << 20)
    data = _rewrapped(_source("jpeg"), b"taken on a phone")
    twin = {"one_byte": _rewrapped(_source("jpeg"), b"taken on a phonE"),
            "trailing_byte": data + b"\0",
            "metadata_added": _rewrapped(data)}[other]
    assert twin != data
    np.testing.assert_array_equal(decode_image(twin), decode_image(data))
    first, _ = r.stage(data, cache)
    second, _ = r.stage(twin, cache)
    assert first[0] == second[0] == "own"
    assert first[1].result(timeout=5)[0] == second[1].result(timeout=5)[0]
    cache.complete(first[3], {"answer": 1})
    cache.complete(second[3], {"answer": 2})
    st = cache.stats()
    assert st["entries"] == 2 and st["misses_total"] == 2
    assert st["digest_bytes_total"] == len(data) + len(twin)
    assert r.stage(data, cache)[0][:2] == ("done", {"answer": 1})
    assert r.stage(twin, cache)[0][:2] == ("done", {"answer": 2})


@pytest.mark.parametrize("exc", [BacklogFull, QuotaExceeded,
                                 DeadlineExceeded, ShuttingDown])
@pytest.mark.parametrize("source", ["jpeg", "png"])
@pytest.mark.parametrize("wire", ["ragged", "rgb", "yuv420"])
def test_a_refused_lease_aborts_the_flight_that_is_older_than_it(
        rig, monkeypatch, wire, source, exc):
    r = rig(wire)
    cache = ResponseCache(1 << 20)
    data = _source(source)
    waiters = []

    def refuse(*a, **kw):
        # While the leader asks for its lease, the same upload arrives on
        # another request and coalesces onto the flight already led.
        monkeypatch.undo()
        waiters.append(r.stage(data, cache)[0])
        raise exc("no room for this one")

    monkeypatch.setattr(r.batcher, NEED[wire][0], refuse)
    with pytest.raises(exc):
        r.stage(data, cache)

    (waiter,) = waiters
    assert waiter[0] == "wait"
    assert isinstance(waiter[1].future.exception(timeout=1), exc)
    assert r.pending() == 0
    assert cache.stats()["inflight"] == 0, "a led flight was leaked"
    # The key leads again at once, and is no wait on a dead flight.
    slot, _ = r.stage(data, cache)
    assert slot[0] == "own" and slot[3] is not waiter[1]
    assert slot[1].result(timeout=5)[0] == sum(HW)
    cache.abort(slot[3], RuntimeError("test over"))
    _settle(r, 1)


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
