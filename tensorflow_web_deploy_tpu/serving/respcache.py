"""Content-addressed response cache with single-flight dedup.

Real user traffic at millions-of-users scale is heavy-tailed: a small set
of hot images accounts for most requests, and the serving stack used to
recompute every one of them from scratch. FlexServe (arxiv 2003.01538)
wins precisely by not re-running inference for repeated inputs, and the
Serverless-Dataflow stage framing (PAPERS.md, adopted in the pipelined
batcher) says the cheapest stage is the one you skip entirely. This
module is that skip:

- **Content-addressed keys.** An entry is keyed by ``(model, version,
  digest, topk, dtype)`` (:func:`make_key`: an f32 entry can never answer
  for an int8 variant). For an image the digest is
  :func:`upload_digest`: blake2b-128 over THE UPLOAD'S BYTES — the part's
  body, free of connection, header order and multipart framing — with
  the canvas bucket set the request may choose from and the wire its
  rows ship on, which with the model version are everything that decides
  which pixels the device sees for those bytes. serving/staging.py builds
  it, for requests and jobs alike, and looks up FIRST: before the header
  probe, the slot lease and any decode, so a hit or a coalesced wait
  costs a hash of a megabyte or two and touches neither a decoder nor
  the batcher. The other choice is the decoded pixels: a digest of what
  the device would see, which can only be taken after the decode into
  the leased row. For a 9 MP phone photo that hashes 27 MB where the
  upload is 1.1 (blake2b is bound by its own arithmetic, so the cost
  follows the bytes: PERF.md section 6, PR 35), and a hit has already paid the
  decode it was meant to skip. What the bytes give up: two uploads whose
  bytes differ and whose decoded pixels are identical (an EXIF block
  stripped or rewritten without re-encoding) are two entries, not one.
  Every answer stays exact — a hit is what the model gave for these very
  bytes — and a non-cryptographic hash was not taken for speed, because
  on a public endpoint a collision is another user's answer.
  Pipeline-DAG stages (serving/dag.py) reuse
  :func:`make_key` with digests of their own: stage 1 the canvas it crops
  from (:func:`canvas_digest`), downstream stages the upstream stage's
  result (:func:`stage_input_digest`) — so each stage caches
  independently and a hot-swap of one stage invalidates exactly that
  stage's entries.

- **Byte-budgeted LRU.** Entries carry the serialized size of their
  formatted payload; over ``max_bytes`` the least-recently-hit entries
  are evicted. ``max_bytes == 0`` disables the cache entirely (the
  ``--cache-bytes 0`` baseline bench.py's ``cache`` block compares
  against).

- **Single-flight dedup.** The first miss for a key becomes the *leader*
  and computes through the normal batch path; concurrent requests for the
  same key *coalesce* onto the leader's in-flight :class:`Flight` and all
  share its result — a viral image costs one device dispatch instead of
  N. Waiters block on the flight's Future OUTSIDE the cache lock (the
  no-blocking-under-lock invariant twdlint enforces).

- **Version-gated invalidation.** Stale reads are impossible *by
  construction*: the key carries the model version, and the registry's
  serving-map flip gates which version a request can resolve — a request
  that resolved version N can only ever see version-N entries. The
  registry additionally calls :meth:`invalidate` (via its retire
  listeners, under ``registry.cond`` — the declared lock order
  registry.cond → cache.lock) the moment a version enters DRAINING: its
  entries are dropped (freeing budget for live versions) and its
  in-flight flights are aborted with :class:`CacheRetired`, so coalesced
  waiters fall through to a miss on the *new* version instead of waiting
  on a drain.

Concurrency: one ``cache.lock`` (declared in tools/twdlint/lockorder.toml
below ``batcher.cond``, above the leaf telemetry locks) guards the entry
map, the flight map, and every counter. Nothing blocking ever runs under
it — lookups are dict ops, and flight resolution happens after release.
"""

from __future__ import annotations

import hashlib
import json
from collections import OrderedDict
from concurrent.futures import Future

import numpy as np

from ..utils.locks import named_lock


class CacheRetired(RuntimeError):
    """The flight a waiter coalesced onto was aborted because its model
    version was retired (hot-swap/unload drain). The HTTP layer retries
    the request once — it re-resolves through the registry, lands on the
    NEW serving version, and proceeds as an ordinary miss."""


def upload_digest(data, buckets, wire: str | None) -> str:
    """Content digest of one uploaded image: blake2b-128 over the upload's
    bytes, led by what else decides the pixels the device sees for them —
    the canvas bucket set the request may choose from (the degradation
    ladder's rung 2 narrows it) and the wire (``ragged``, ``rgb``,
    ``yuv420``; None for an engine without leases). Model, version, topk
    and dtype are :func:`make_key`'s. The rule: two requests whose
    device-side pixels could differ never share a digest.

    The context goes first and ends in the length of ``data``, so no
    upload's tail can pass for another request's context. blake2b and not
    a faster hash: a collision here is another user's answer.
    """
    h = hashlib.blake2b(digest_size=16)
    h.update(b"%s|%s|%d|" % (
        (wire or "").encode(),
        ",".join(str(int(s)) for s in buckets).encode(), len(data)))
    h.update(data)
    return h.hexdigest()


def canvas_digest(canvas, hw) -> str:
    """Content digest of one decoded canvas: its bytes (wire format —
    exactly what the device would see) plus the valid (h, w). The
    two-stage pipeline (serving/dag.py) keys its first stage on it: the
    canvas is what it crops from, and it decodes before it looks up.
    ``/predict`` and ``/jobs`` key on :func:`upload_digest` instead.

    The hw rides along because the canvas alone cannot distinguish an
    image whose edge pixels are genuinely black from zero padding. The
    native decoder memsets the whole canvas before writing pixels, and the
    PIL fallback pads onto a fresh zeroed canvas, so the digest is
    deterministic across staging-slab reuse. blake2b-128: fast in pure
    stdlib, and 128 bits makes accidental collision odds negligible at any
    realistic cache size.
    """
    arr = np.asarray(canvas)
    if not arr.flags["C_CONTIGUOUS"]:
        arr = np.ascontiguousarray(arr)
    h = hashlib.blake2b(digest_size=16)
    h.update(arr.data)
    h.update(b"%d,%d" % (int(hw[0]), int(hw[1])))
    return h.hexdigest()


def stage_input_digest(upstream_digest: str, upstream_payload: dict) -> str:
    """Content digest for a non-first pipeline-DAG stage.

    A downstream stage's input is not pixels — it is the upstream stage's
    *result* applied to the original image (kept boxes selecting crops of
    the staged canvas). Hashing the request digest together with the
    canonical upstream payload gives exactly the right equivalence class:
    a detection cache hit after a classifier swap reproduces the same
    stage-2 key prefix input (same boxes, same image) while any change in
    what the upstream stage actually answered — different boxes after a
    detector swap, different topk — re-keys the downstream stage. The
    upstream stage's serving version deliberately does NOT ride in this
    digest (it lives in the upstream stage's own key): two detector
    versions that agree bit-for-bit on an image may share classifier
    work, which is the memoization the dataflow framing promises.
    """
    h = hashlib.blake2b(digest_size=16)
    h.update(upstream_digest.encode())
    h.update(b"|")
    h.update(_canonical_payload(upstream_payload))
    return h.hexdigest()


def _canonical_payload(payload: dict) -> bytes:
    """One canonical serialization per payload: the ETag hashes it and the
    LRU budget counts its bytes, so computing it once per miss keeps the
    hot path at a single dumps."""
    return json.dumps(
        payload, sort_keys=True, separators=(",", ":"), default=str
    ).encode()


def _etag_of(body: bytes, model: str, version) -> str:
    h = hashlib.blake2b(digest_size=12)
    h.update(body)
    h.update(f"|{model}@{version}".encode())
    return h.hexdigest()


def payload_etag(payload: dict, model: str, version) -> str:
    """Stable response digest for the HTTP ETag: a hash of the formatted
    per-image payload plus the serving identity. Deliberately NOT a hash
    of the full response body — the envelope carries per-request fields
    (latency_ms, trace_id) that must not defeat If-None-Match."""
    return _etag_of(_canonical_payload(payload), model, version)


class Flight:
    """One in-flight computation for a cache key. The leader computes and
    calls :meth:`ResponseCache.complete` / :meth:`ResponseCache.abort`;
    waiters block on :attr:`future` (resolves to ``(payload, etag)``)."""

    __slots__ = ("key", "model", "future")

    def __init__(self, key: tuple, model: str):
        self.key = key
        self.model = model
        self.future: Future = Future()


class _Entry:
    __slots__ = ("key", "payload", "etag", "nbytes")

    def __init__(self, key: tuple, payload: dict, etag: str, nbytes: int):
        self.key = key
        self.payload = payload
        self.etag = etag
        self.nbytes = nbytes


def make_key(model: str, version, digest: str, topk: int,
             dtype: str = "bfloat16") -> tuple:
    """The canonical cache key. ``(model, version)`` lead so invalidation
    and per-model accounting can match on a prefix. ``dtype`` keys the
    serving tier: an f32→int8 hot-swap under one name answers within the
    parity tolerance but NOT bit-identically, so a cached f32 payload
    must never serve as an int8 hit (stale-tier hits are the quant
    hot-swap test's zero-tolerance assertion)."""
    return (model, version, digest, int(topk), dtype)


class ResponseCache:
    """Byte-budgeted LRU of formatted per-image responses + the
    single-flight table. One instance per App; every model's entries share
    the byte budget (per-model usage is visible in :meth:`stats`)."""

    def __init__(self, max_bytes: int):
        self.max_bytes = max(0, int(max_bytes))
        self._lock = named_lock("cache.lock")
        self._entries: OrderedDict[tuple, _Entry] = OrderedDict()
        self._inflight: dict[tuple, Flight] = {}
        # (model, version) pairs retired by the registry: a leader that
        # completes AFTER its version drained must not re-insert an entry
        # nothing can ever look up again. Bounded by versions-ever-loaded.
        self._retired: set[tuple] = set()
        self.bytes = 0
        self._hits = 0
        self._misses = 0
        self._coalesced = 0
        self._evictions = 0
        self._invalidations = 0
        self._inserts = 0
        # Bytes hashed to build the keys looked up here: what says which
        # representation of an image the digest runs over (an upload is a
        # megabyte or two, its decoded pixels tens).
        self._digest_bytes = 0
        # Bulk-tier split (serving/jobs.py): job lookups ride the same
        # entry/flight maps — that is the dedup-for-free — but count
        # apart, so the interactive hit rate dashboards read is not
        # diluted (or inflated) by a batch job sweeping the corpus.
        self._bulk_hits = 0
        self._bulk_misses = 0
        self._bulk_coalesced = 0
        self._per_model: dict[str, dict] = {}

    @property
    def enabled(self) -> bool:
        return self.max_bytes > 0

    # -------------------------------------------------------------- lookup

    def _model_counters(self, model: str) -> dict:
        m = self._per_model.get(model)
        if m is None:
            # hits/misses/coalesced are the INTERACTIVE tier only — the
            # per-model ratio operators watch must not crater because a
            # job swept a cold corpus. Bulk lookups count in bulk_*;
            # entries/bytes are shared (one entry map serves both tiers).
            m = self._per_model[model] = {
                "hits": 0, "misses": 0, "coalesced": 0,
                "bulk_hits": 0, "bulk_misses": 0, "bulk_coalesced": 0,
                "entries": 0, "bytes": 0,
            }
        return m

    def begin(self, key: tuple, model: str, bulk: bool = False,
              digest_bytes: int = 0):
        """One lookup: ``("hit", entry)`` for a cached result, ``("wait",
        flight)`` to coalesce onto an in-flight leader (block on
        ``flight.future`` OUTSIDE any lock), or ``("lead", flight)`` —
        the caller computes and MUST end the flight with :meth:`complete`
        or :meth:`abort` (a leaked flight would wedge every later waiter
        until their request timeouts). ``bulk=True`` marks a job-tier
        lookup: same maps (bulk and interactive dedup against each
        other), separate counters. ``digest_bytes`` is how many bytes the
        caller hashed for ``key`` (``digest_bytes_total`` in
        :meth:`stats`)."""
        with self._lock:
            self._digest_bytes += digest_bytes
            entry = self._entries.get(key)
            if entry is not None:
                self._entries.move_to_end(key)
                if bulk:
                    self._bulk_hits += 1
                else:
                    self._hits += 1
                self._model_counters(model)[
                    "bulk_hits" if bulk else "hits"] += 1
                return "hit", entry
            flight = self._inflight.get(key)
            if flight is not None:
                if bulk:
                    self._bulk_coalesced += 1
                else:
                    self._coalesced += 1
                self._model_counters(model)[
                    "bulk_coalesced" if bulk else "coalesced"] += 1
                return "wait", flight
            if bulk:
                self._bulk_misses += 1
            else:
                self._misses += 1
            self._model_counters(model)[
                "bulk_misses" if bulk else "misses"] += 1
            flight = Flight(key, model)
            self._inflight[key] = flight
            return "lead", flight

    # ------------------------------------------------------------ complete

    def complete(self, flight: Flight, payload: dict) -> str:
        """Leader path: insert the formatted payload, resolve every
        coalesced waiter, return the entry's ETag."""
        key = flight.key
        body = _canonical_payload(payload)
        etag = _etag_of(body, key[0], key[1])
        nbytes = len(body)
        with self._lock:
            if self._inflight.get(key) is flight:
                del self._inflight[key]
            store = (
                self.enabled
                and key[:2] not in self._retired
                and nbytes <= self.max_bytes
                and key not in self._entries
            )
            if store:
                entry = _Entry(key, payload, etag, nbytes)
                self._entries[key] = entry
                self.bytes += nbytes
                self._inserts += 1
                m = self._model_counters(key[0])
                m["entries"] += 1
                m["bytes"] += nbytes
                while self.bytes > self.max_bytes and self._entries:
                    _, victim = self._entries.popitem(last=False)
                    self.bytes -= victim.nbytes
                    self._evictions += 1
                    vm = self._model_counters(victim.key[0])
                    vm["entries"] -= 1
                    vm["bytes"] -= victim.nbytes
        # Resolve waiters OUTSIDE the lock: set_result wakes threads that
        # may immediately re-enter the cache.
        try:
            flight.future.set_result((payload, etag))
        except Exception:
            pass  # aborted by an invalidation racing the completion
        return etag

    def abort(self, flight: Flight, exc: BaseException) -> None:
        """Leader failed (batch error, timeout, shutdown): fail every
        coalesced waiter with the leader's exception so they answer (or
        retry) instead of hanging to their own timeouts."""
        with self._lock:
            if self._inflight.get(flight.key) is flight:
                del self._inflight[flight.key]
        try:
            flight.future.set_exception(exc)
        except Exception:
            pass  # already resolved/aborted

    # ---------------------------------------------------------- invalidate

    def invalidate(self, model: str, version) -> int:
        """Drop every entry of ``(model, version)`` and abort its in-flight
        flights with :class:`CacheRetired` (waiters fall through to a miss
        on the successor version). Called by the registry's retire
        listener under ``registry.cond`` — registry.cond ranks above
        cache.lock, so the nesting is a declared-order climb; nothing here
        blocks. Returns the number of entries dropped."""
        prefix = (model, version)
        aborted: list[Flight] = []
        with self._lock:
            self._retired.add(prefix)
            doomed = [k for k in self._entries if k[:2] == prefix]
            for k in doomed:
                victim = self._entries.pop(k)
                self.bytes -= victim.nbytes
                m = self._model_counters(model)
                m["entries"] -= 1
                m["bytes"] -= victim.nbytes
            self._invalidations += len(doomed)
            for k in [k for k in self._inflight if k[:2] == prefix]:
                aborted.append(self._inflight.pop(k))
        for flight in aborted:
            try:
                flight.future.set_exception(CacheRetired(
                    f"{model}@{version} retired while this key was in flight"
                ))
            except Exception:
                pass
        return len(doomed)

    # --------------------------------------------------------------- stats

    def stats(self) -> dict:
        """The ``/stats`` "cache" block (and /metrics' source): totals are
        cumulative counters, bytes/entries/inflight are live gauges."""
        with self._lock:
            lookups = self._hits + self._misses + self._coalesced
            return {
                "enabled": self.enabled,
                "max_bytes": self.max_bytes,
                "bytes": self.bytes,
                "entries": len(self._entries),
                "inflight": len(self._inflight),
                "hits_total": self._hits,
                "misses_total": self._misses,
                "coalesced_total": self._coalesced,
                "evictions_total": self._evictions,
                "invalidations_total": self._invalidations,
                "inserts_total": self._inserts,
                "digest_bytes_total": self._digest_bytes,
                "hit_rate": (
                    round(self._hits / lookups, 4) if lookups else None
                ),
                # Job-tier lookups (separate so a corpus sweep can't skew
                # the interactive hit-rate above); "coalesced" includes
                # duplicates WITHIN one job's own chunks — the dedup a
                # duplicate-heavy manifest gets for free.
                "bulk": {
                    "hits_total": self._bulk_hits,
                    "misses_total": self._bulk_misses,
                    "coalesced_total": self._bulk_coalesced,
                },
                "per_model": {
                    name: dict(c)
                    for name, c in sorted(self._per_model.items())
                },
            }
