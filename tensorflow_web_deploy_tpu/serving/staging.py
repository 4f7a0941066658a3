"""One way in: an image's bytes become one slot of an assembling batch.

``POST /predict`` (serving/http.py) and the bulk job runner
(serving/jobs.py) both stage through :func:`stage_image`; this module is
the only place that knows the wire — which header probe, lease, native
decode and PIL fallback go together (:class:`_Wire`) — and the only place
that builds a response-cache key for an upload.

The order: the cache is asked FIRST, once, on every path. The key is a
digest of the upload's bytes with the canvas bucket set and the wire
(respcache.py ``upload_digest``; what keying by bytes and not by decoded
pixels gives up is said there), so a hit or a coalesced wait returns
before the header probe, the lease and any decode, and touches neither a
decoder nor the batcher. A miss leads the flight and goes on: a JPEG the
native decoder takes is probed, leased and decoded INTO the leased memory
(the image's single host copy), then committed. A native decode that
fails after its header parsed gives the lease back (the row ships as a
hole) and falls through to PIL under the flight it already leads. A PIL
image is decoded and fitted, then leased and committed with its pixels.
Engines without slot leases (mocks, embedders) decode with
``engine.prepare_bytes`` and ``batcher.submit``.

The unwind: whatever leaves :func:`stage_image` by an exception leaves
nothing behind for this image — the lease released, then the led flight
aborted, each guarded so neither can starve the other (a PENDING slot
holds its whole builder back until the lease timeout; a leaked flight
wedges every coalesced waiter until theirs). The flight is older than the
lease, so what the lease's admission raises (BacklogFull, QuotaExceeded,
DeadlineExceeded, ShuttingDown) aborts it too. The request's EARLIER
slots are the caller's, through :func:`abort_slots`.

The timing: every stretch of decode work is a ``stage(span,
"image_decode")`` block, the digest plus lookup one ``stage(span,
"cache_lookup")`` block (utils/tracing.py), so both callers' spans, the
``twd.*`` annotations and the per-layer metrics read one set of stamps.
"""

from __future__ import annotations

from typing import Callable, NamedTuple

from .. import native
from ..ops.image import (
    decode_image, fit_to_bucket, pad_to_canvas, rgb_to_yuv420_canvas,
)
from ..utils.tracing import stage
from .overload import Degraded
from .respcache import make_key, upload_digest


class UndecodableImage(Exception):
    """The bytes are no image any decoder here takes (or chaos said so:
    ``note`` then names the injection). The caller maps it: 400 for a
    request, an error line for a job's item."""

    def __init__(self, note: str = ""):
        super().__init__("could not decode image" + note)
        self.note = note


class _Wire(NamedTuple):
    """The four pieces that go together on one wire of leased rows."""

    # (data, buckets) -> (canvas bucket, need, original (h, w)) | None:
    # the JPEG header probe; ``need`` is what ``lease`` reserves.
    plan: Callable
    # (batcher, need, canvas bucket, **kw) -> SlotLease
    lease: Callable
    # (data, lease.row, canvas bucket) -> decoded (h, w) | None
    decode: Callable
    # (PIL-decoded image, buckets) -> (pixels, (h, w), canvas bucket, need)
    fit: Callable


def _plan_ragged(data, buckets):
    plan = native.plan_decode_packed(data, buckets)
    if plan is None:
        return None
    s, need, _decoded_hw, orig = plan
    return s, need, orig


def _fit_ragged(img, buckets):
    tight, hw, s = fit_to_bucket(img, buckets)
    return tight, hw, s, hw[0] * hw[1] * 3


# Tight bytes at native stride in a flat arena, unpacked onto the canvas
# bucket on the device.
_RAGGED = _Wire(
    plan=_plan_ragged,
    lease=lambda batcher, need, s, **kw: batcher.lease_ragged(need, s, **kw),
    decode=native.decode_packed_into,
    fit=_fit_ragged,
)


def _classic(wire: str) -> _Wire:
    """Padded canvas rows, rgb or I420 planes; the decoder zero/neutral-pads
    the whole row."""

    def fit(img, buckets):
        canvas, hw = pad_to_canvas(img, buckets)
        if wire == "yuv420":
            canvas = rgb_to_yuv420_canvas(canvas)
        return canvas, hw, None, tuple(canvas.shape)

    return _Wire(
        plan=lambda data, buckets: native.plan_decode(data, buckets, wire),
        lease=lambda batcher, shape, s, **kw: batcher.lease(shape, **kw),
        # A slab without row views takes the PIL path's canvas copy.
        decode=lambda data, row, s: (
            native.decode_into_row(data, row, s, wire)
            if row is not None else None),
        fit=fit,
    )


_WIRES = {"ragged": _RAGGED, "rgb": _classic("rgb"),
          "yuv420": _classic("yuv420")}


def _wire_of(batcher, mv) -> str | None:
    """The name of the wire this batcher's builders speak (a key of
    ``_WIRES``, and part of the cache key); None for an engine without
    slot-lease slabs, whose only way in is ``batcher.submit``."""
    if not getattr(batcher, "supports_lease", False):
        return None
    if getattr(batcher, "ragged", False):
        return "ragged"
    cfg = getattr(mv.engine, "cfg", None)
    return getattr(cfg, "wire_format", "rgb")


def stage_image(data: bytes, *, batcher, mv, cache, topk: int, buckets,
                span, bulk: bool = False, tenant: str | None = None,
                deadline: float | None = None, shed_misses: bool = False,
                chaos=None) -> tuple:
    """Stage one image against model version ``mv``. Returns its slot:
    ``("done", payload, etag)`` served from the cache; ``("wait", flight)``
    coalesced onto another caller's computation of the same content;
    ``("own", future, orig_hw, flight, lease)`` computing here (``flight``
    the led single-flight, None with ``cache`` None; ``lease`` None for
    an engine without leases).

    The cache is asked first, keyed by ``data`` itself: a "done" or a
    "wait" has taken no lease and called no decoder. ``buckets`` are the
    canvas buckets to choose from, and part of the key; ``span`` is the
    request's Span, which rides the lease into its batch too.
    ``bulk=True`` is the job runner's: bulk builders, bulk cache counters,
    a ``span`` that need only take ``add(stage, seconds)`` and stays off
    the lease — and the SAME key as ``bulk=False`` for the same bytes,
    which is what lets a job's misses warm the interactive tier.
    ``deadline`` and ``tenant`` feed the batcher's admission.
    ``shed_misses`` (the degradation ladder's reject rung) raises
    :class:`Degraded` for a miss, between lookup and commit; hits and
    waits still answer. Raises :class:`UndecodableImage`, and whatever the
    batcher's admission does (ShuttingDown, BacklogFull, QuotaExceeded,
    DeadlineExceeded)."""
    if chaos is not None and chaos.decode_fault():
        raise UndecodableImage(" (chaos: injected decode failure)")
    wire_name = _wire_of(batcher, mv)
    wire = _WIRES[wire_name] if wire_name is not None else None
    admit = dict(span=None if bulk else span, bulk=bulk, deadline=deadline,
                 tenant=tenant)

    def shed_if_asked():
        if shed_misses:
            raise Degraded("shedding cache-miss work under overload "
                           "(degradation reject rung)")

    lease = flight = None
    try:
        if cache is not None:  # disabled: no lookup, and no stage
            with stage(span, "cache_lookup"):
                key = make_key(mv.name, mv.version,
                               upload_digest(data, buckets, wire_name), topk,
                               getattr(mv.model_cfg, "dtype", "bfloat16"))
                kind, obj = cache.begin(key, mv.name, bulk=bulk,
                                        digest_bytes=len(data))
            if kind == "hit":
                return "done", obj.payload, obj.etag
            if kind == "wait":
                return "wait", obj
            flight = obj
        if wire is not None:
            with stage(span, "image_decode"):  # header probe
                plan = wire.plan(data, buckets)
            if plan is not None:
                s, need, orig = plan
                lease = wire.lease(batcher, need, s, **admit)
                # The C side re-validates the row's capacity: an overrun
                # would corrupt a NEIGHBOURING image's bytes.
                with stage(span, "image_decode"):
                    hw = wire.decode(data, lease.row, s)
                if hw is not None:
                    shed_if_asked()
                    lease.commit(hw)
                    return "own", lease.future, orig, flight, lease
                # The header parsed and the stream did not decode: the row
                # ships as a hole, PIL gets a try under the same flight.
                lease.release()
                lease = None
        try:
            with stage(span, "image_decode"):
                if wire is None:
                    pixels, hw, orig = mv.engine.prepare_bytes(data)
                else:
                    img = decode_image(data)
        except Exception:
            raise UndecodableImage() from None
        if wire is None:
            shed_if_asked()
            return ("own", batcher.submit(pixels, hw, **admit), orig, flight,
                    None)
        orig = (img.shape[0], img.shape[1])
        with stage(span, "image_decode"):
            pixels, hw, s, need = wire.fit(img, buckets)
        shed_if_asked()
        lease = wire.lease(batcher, need, s, **admit)
        lease.commit(hw, canvas=pixels)
        return "own", lease.future, orig, flight, lease
    except BaseException as e:
        try:
            if lease is not None:
                lease.release()
        finally:
            if flight is not None:
                cache.abort(flight, e)
        raise


def abort_slots(slots, cache, exc: BaseException) -> None:
    """Unwind slots that :func:`stage_image` returned and nobody will wait
    for (a sibling upload was refused, a deadline passed, a job was
    interrupted): cancel and release the OWN ones — a committed row that
    has not left becomes a hole, one that has is past saving and its
    result dropped — and abort their led flights so coalesced waiters fail
    over at once. "done" and "wait" slots hold nothing: other callers own
    those computations."""
    for slot in slots:
        if slot[0] != "own":
            continue
        _, future, _orig, flight, lease = slot
        try:
            future.cancel()
        except Exception:
            pass
        if lease is not None:
            try:
                lease.release()
            except Exception:
                pass
        if flight is not None and cache is not None:
            cache.abort(flight, exc)
