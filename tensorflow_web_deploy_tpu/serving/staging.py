"""One way in: an image's bytes become one slot of an assembling batch.

``POST /predict`` (serving/http.py) and the bulk job runner
(serving/jobs.py) both stage through :func:`stage_image`; this module is
the only place that knows the wire — which header probe, lease, native
decode, PIL fallback and content digest go together (:class:`_Wire`) —
and the only place that builds a response-cache key for pixels.

The order: a JPEG the native decoder takes is probed, leased, decoded INTO
the leased memory (the image's single host copy), then digested and looked
up; a hit or a coalesced wait gives the lease back, so the row ships as a
hole and costs no device work; a miss commits. A native decode that fails
after its header parsed gives the lease back and falls through to PIL. A
PIL image is digested and looked up BEFORE any lease, so a hit never
touches the batcher. Engines without slot leases (mocks, embedders) decode
with ``engine.prepare_bytes`` and ``batcher.submit`` their misses.

The unwind: whatever leaves :func:`stage_image` by an exception leaves
nothing behind for this image — the lease released, then the led flight
aborted, each guarded so neither can starve the other (a PENDING slot
holds its whole builder back until the lease timeout; a leaked flight
wedges every coalesced waiter until theirs). The request's EARLIER slots
are the caller's, through :func:`abort_slots`.

The timing: every stretch of decode work is a ``stage(span,
"image_decode")`` block, every digest plus lookup a ``stage(span,
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
from .respcache import canvas_digest, make_key, packed_digest


class UndecodableImage(Exception):
    """The bytes are no image any decoder here takes (or chaos said so:
    ``note`` then names the injection). The caller maps it: 400 for a
    request, an error line for a job's item."""

    def __init__(self, note: str = ""):
        super().__init__("could not decode image" + note)
        self.note = note


class _Wire(NamedTuple):
    """The five pieces that go together on one wire of leased rows."""

    # (data, buckets) -> (canvas bucket, need, original (h, w)) | None:
    # the JPEG header probe; ``need`` is what ``lease`` reserves.
    plan: Callable
    # (batcher, need, canvas bucket, **kw) -> SlotLease
    lease: Callable
    # (data, lease.row, canvas bucket) -> decoded (h, w) | None
    decode: Callable
    # (PIL-decoded image, buckets) -> (pixels, (h, w), canvas bucket, need)
    fit: Callable
    # (pixels, (h, w), canvas bucket) -> content digest
    digest: Callable


def _plan_ragged(data, buckets):
    plan = native.plan_decode_packed(data, buckets)
    if plan is None:
        return None
    s, need, _decoded_hw, orig = plan
    return s, need, orig


def _fit_ragged(img, buckets):
    tight, hw, s = fit_to_bucket(img, buckets)
    return tight, hw, s, hw[0] * hw[1] * 3


# Tight bytes at native stride in a flat arena; the digest hashes them with
# (h, w) and the canvas bucket — the same equivalence classes as a padded
# canvas's, because the device-side unpack is a function of those three.
_RAGGED = _Wire(
    plan=_plan_ragged,
    lease=lambda batcher, need, s, **kw: batcher.lease_ragged(need, s, **kw),
    decode=native.decode_packed_into,
    fit=_fit_ragged,
    digest=packed_digest,
)


def _canvas_digest(canvas, hw, s):
    return canvas_digest(canvas, hw)


def _classic(wire: str) -> _Wire:
    """Padded canvas rows, rgb or I420 planes. The decoder zero/neutral-pads
    the whole row, so its digest is deterministic across slab reuse."""

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
        digest=_canvas_digest,
    )


_CLASSIC = {wire: _classic(wire) for wire in ("rgb", "yuv420")}


def _wire_of(batcher, mv) -> _Wire | None:
    """The wire this batcher's builders speak; None for an engine without
    slot-lease slabs, whose only way in is ``batcher.submit``."""
    if not getattr(batcher, "supports_lease", False):
        return None
    if getattr(batcher, "ragged", False):
        return _RAGGED
    cfg = getattr(mv.engine, "cfg", None)
    return _CLASSIC[getattr(cfg, "wire_format", "rgb")]


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

    ``buckets`` are the canvas buckets to choose from; ``span`` is the
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
    wire = _wire_of(batcher, mv)
    digest = wire.digest if wire is not None else _canvas_digest
    admit = dict(span=None if bulk else span, bulk=bulk, deadline=deadline,
                 tenant=tenant)

    def lookup(pixels, hw, s):
        """``(None, None)``, and no stage, with the cache disabled."""
        if cache is None:
            return None, None
        with stage(span, "cache_lookup"):
            key = make_key(mv.name, mv.version, digest(pixels, hw, s), topk,
                           getattr(mv.model_cfg, "dtype", "bfloat16"))
            return cache.begin(key, mv.name, bulk=bulk)

    def answered(kind, obj):
        return (("done", obj.payload, obj.etag) if kind == "hit"
                else ("wait", obj))

    def shed_if_asked():
        if shed_misses:
            raise Degraded("shedding cache-miss work under overload "
                           "(degradation reject rung)")

    lease = flight = None
    try:
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
                if hw is None:
                    # The header parsed and the stream did not decode: the
                    # row ships as a hole, PIL gets a try.
                    lease.release()
                    lease = None
                else:
                    kind, obj = lookup(lease.row, hw, s)
                    if kind in ("hit", "wait"):
                        lease.release()
                        lease = None
                        return answered(kind, obj)
                    flight = obj
                    shed_if_asked()
                    lease.commit(hw)
                    return "own", lease.future, orig, flight, lease
        # Decoded outside any lease, so the digest comes before one.
        try:
            with stage(span, "image_decode"):
                if wire is None:
                    pixels, hw, orig = mv.engine.prepare_bytes(data)
                else:
                    img = decode_image(data)
        except Exception:
            raise UndecodableImage() from None
        s = None
        if wire is not None:
            orig = (img.shape[0], img.shape[1])
            with stage(span, "image_decode"):
                pixels, hw, s, need = wire.fit(img, buckets)
        kind, obj = lookup(pixels, hw, s)
        if kind in ("hit", "wait"):
            return answered(kind, obj)
        flight = obj
        shed_if_asked()
        if wire is None:
            return ("own", batcher.submit(pixels, hw, **admit), orig, flight,
                    None)
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
