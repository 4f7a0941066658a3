"""Request-scoped span tracing: one trace ID + per-stage wall-time record
carried through the whole request path (accept → socket read → slot lease
→ decode-into-slab → staging commit → assembly wait → dispatch → device →
postprocess → serialize). Canonical stage names on the serving path:
``http_read``, ``body_read``, ``lease_wait`` (blocked acquiring a batch
slot under backpressure), ``image_decode`` (wire bytes → slab row, GIL
released; only a cache miss pays it), ``cache_lookup`` (digest of the
upload's bytes with bucket set and wire + response-cache consult, before
the lease and the decode), ``cache_wait`` (coalesced onto another request's
in-flight computation for the same content key — single-flight dedup),
``staging_write`` (slot commit / fallback canvas copy),
``queue_wait`` (commit → launch start), then the batch's flight as the
engine stamps it (``serving/engine.py::Flight``): ``device_transfer``
(launch start → the H2D copy landed), ``device_execute`` (copy landed →
outputs computed: the wait behind earlier calls on the device, then the
device's own work) and ``device_d2h`` (outputs computed → on the host);
an engine that stamps no flight gets ``device_dispatch`` (launch → execute
enqueued) and ``device_execute`` (→ outputs on host) instead. Then
``postprocess``, ``serialize``. Under the pipelined batcher, one
request's ``device_execute`` interval routinely overlaps ANOTHER
request's ``image_decode``/``device_transfer`` — that concurrency is the
point, and bench.py's ``pipeline`` block measures it from the batcher's
batch timeline.

:func:`stage` is the one helper that times a stage: it adds the interval to
the request's span *and* opens a ``jax.profiler.TraceAnnotation`` named
``twd.<name>`` (plus a low-cardinality label such as ``c4096 b32``), with
identities (``seq=``, ``rows=``, ``trace_id=``) as keyword arguments, which
the profiler keeps as event stats. So a ``POST /debug/trace`` recording
holds the program's own stages on every thread, next to the device's ops.
An annotation's name is not always its span stage's: the engine's
``twd.h2d`` / ``twd.unpack_enqueue`` / ``twd.serve_enqueue`` /
``twd.d2h_start`` time the *enqueues* (``jax.device_put`` returns before
the bytes have crossed) and have no stage; ``twd.h2d_flight`` is the copy
itself, opened as the ``device_put`` starts and closed by the thread that
waited for it to land, so it ends where ``device_transfer`` does;
``twd.fetch`` (the wait for the outputs, then their conversion) covers
the end of ``device_execute`` and ``device_d2h``; ``twd.await_batch`` /
``twd.seal_wait`` have no stage at all (another layer already stamps
those intervals).

A ``Span`` is created by the HTTP front end at request-accept time (or by
the WSGI app itself for embedded callers), travels via the WSGI environ
(``environ["tpu_serve.span"]``) and the batcher's ``_Request``, and is
stamped by whichever layer owns each stage. The completed span is folded
into :class:`~..utils.metrics.Observability` (per-stage histograms, the
slow-request flight recorder, the JSON access log) and its trace ID is
returned in the ``X-Trace-Id`` response header.

Stage durations are ``time.monotonic()`` deltas — the monotonic-clock
invariant from utils/metrics.py applies: a wall-clock step must never
stretch or collapse a recorded stage. Only the access log carries a
wall-clock timestamp, and only so external tools can join on it.

Concurrency: a span is handed off between threads (HTTP worker → batcher
dispatcher → fetcher → HTTP worker); on the happy path the batcher stamps
device stages *before* resolving the request's future, so the HTTP worker
resumes with the span effectively its alone. But on timeout/shutdown
paths the handler finalizes the span while its _Request objects still sit
in the batcher, whose threads keep stamping — so every stage mutation and
every read-out goes through a per-span lock. ``add_max`` exists for
fan-out requests (one multi-image request whose images ride concurrent
batches): concurrent stages merge as the slowest leg, so the stage sum
still tiles the request's wall time.
"""

from __future__ import annotations

import itertools
import re
import time

from jax.profiler import TraceAnnotation

from .locks import named_lock

# Inbound X-Trace-Id values must be safe to echo into headers, JSON logs,
# and /debug/slow — anything else gets a fresh server-side ID.
_TRACE_ID_RE = re.compile(r"^[A-Za-z0-9._\-]{1,64}$")

# Monotonically-derived trace IDs: a per-process prefix taken from the
# monotonic clock at import plus an atomic counter — unique within the
# process by the counter, disambiguated across restarts by the prefix.
_PREFIX = f"{time.monotonic_ns() & 0xFFFFFFFFFF:010x}"
_counter = itertools.count(1)
_counter_lock = named_lock("trace.id_lock")


def new_trace_id() -> str:
    with _counter_lock:
        n = next(_counter)
    return f"{_PREFIX}-{n:08x}"


def accept_trace_id(inbound: str | None) -> str:
    """Propagate a well-formed inbound trace ID; mint one otherwise."""
    if inbound and _TRACE_ID_RE.match(inbound):
        return inbound
    return new_trace_id()


class Span:
    """One request's trace: named stage durations plus light metadata.

    Stage stamps and read-outs are lock-guarded: a timed-out request is
    finalized by the HTTP worker while its legs still sit in the batcher,
    whose dispatcher/fetcher threads may stamp concurrently — without the
    lock that is a dict-mutation-during-iteration crash on exactly the
    overloaded-server path the 504 exists for. Stamps that land after
    ``finish`` copied the stages are simply not reported — fine, the
    request already answered without them."""

    __slots__ = ("trace_id", "t0", "stages", "meta", "status", "finished_at",
                 "_lock")

    def __init__(self, trace_id: str | None = None, t0: float | None = None):
        self.trace_id = trace_id or new_trace_id()
        self.t0 = time.monotonic() if t0 is None else t0
        self.stages: dict[str, float] = {}  # name -> seconds, insertion order
        self.meta: dict = {}
        self.status: int | None = None
        self.finished_at: float | None = None  # monotonic, set by finish()
        self._lock = named_lock("span.lock")

    def add(self, stage: str, dur_s: float) -> None:
        """Accumulate a serial stage (repeat stamps sum)."""
        with self._lock:
            self.stages[stage] = self.stages.get(stage, 0.0) + max(0.0, dur_s)

    def add_max(self, stage: str, dur_s: float) -> None:
        """Merge a concurrent stage (repeat stamps keep the slowest leg) —
        used for batcher/device stages, where a multi-image request's legs
        overlap and summing them would overshoot the request's wall time."""
        with self._lock:
            self.stages[stage] = max(self.stages.get(stage, 0.0), dur_s)

    def note(self, key: str, value) -> None:
        """Attach metadata (path, image count, batch bucket) — same lock as
        the stage stamps, for the same cross-thread finalize reason."""
        with self._lock:
            self.meta[key] = value

    def note_default(self, key: str, value) -> None:
        with self._lock:
            self.meta.setdefault(key, value)

    def note_append(self, key: str, value) -> None:
        """Metadata that accumulates (the ``batches`` a multi-image request
        rode): a list without repeats, in arrival order."""
        with self._lock:
            have = self.meta.setdefault(key, [])
            if value not in have:
                have.append(value)

    def stages_copy(self) -> dict[str, float]:
        """Consistent copy for aggregation — safe against in-flight stamps."""
        with self._lock:
            return dict(self.stages)

    def finish(self, status: int) -> float:
        """Seal the span; returns total end-to-end seconds. Idempotent so a
        double finalize (app + handler mis-wiring) can't double-count."""
        with self._lock:
            if self.finished_at is None:
                self.finished_at = time.monotonic()
                self.status = status
            return self.finished_at - self.t0

    @property
    def total_s(self) -> float:
        return ((self.finished_at if self.finished_at is not None
                 else time.monotonic()) - self.t0)

    def stage_sum_s(self) -> float:
        return sum(self.stages_copy().values())

    def to_dict(self) -> dict:
        with self._lock:
            stages = dict(self.stages)
            meta = dict(self.meta)
        return {
            "trace_id": self.trace_id,
            "status": self.status,
            "total_ms": round(self.total_s * 1e3, 3),
            "stages_ms": {k: round(v * 1e3, 3) for k, v in stages.items()},
            **({"meta": meta} if meta else {}),
        }


class stage:
    """Time one stage: ``with stage(span, "image_decode"): ...`` adds the
    block's ``time.monotonic()`` interval to ``span`` under ``name`` (repeat
    stamps sum, as :meth:`Span.add`) and holds a profiler annotation
    ``twd.<name>[ <label>]`` open over it, ``ids`` as its stats. ``span=None``
    is an annotation only. ``t0``/``t1`` are the block's own clock reads, for
    a caller that stamps several spans from them. Inactive, the annotation
    costs about a microsecond (PERF.md section 6, PR 27)."""

    __slots__ = ("span", "name", "t0", "t1", "_ann")

    def __init__(self, span, name: str, label: str = "", **ids):
        self.span = span
        self.name = name
        self.t0 = self.t1 = None
        self._ann = TraceAnnotation(
            f"twd.{name} {label}" if label else f"twd.{name}", **ids)

    def __enter__(self):
        self._ann.__enter__()
        self.t0 = time.monotonic()
        return self

    def __exit__(self, *exc):
        self.t1 = time.monotonic()
        self._ann.__exit__(None, None, None)
        if self.span is not None:
            self.span.add(self.name, self.t1 - self.t0)
        return False


def clock_marker() -> float:
    """Emit the ``twd.clock`` annotation that ties the profiler's clock
    (event times count from the recording's own start) to
    ``time.monotonic()``: the event's ``mono_ns`` stat less its
    ``start_ns`` is the offset that puts ``batch_timeline``, ``GET
    /debug/trace`` and the request spans on a recording's time axis.
    Returns the monotonic seconds the marker carries."""
    ns = time.monotonic_ns()
    with TraceAnnotation("twd.clock", mono_ns=ns):
        pass
    return ns / 1e9


# ----------------------------------------------------- chrome trace export


# Batch-record fields that ride a batch event's ``args`` beside the
# identity ones (Batcher._hand_off names them all).
_BATCH_ARGS = ("reason", "t_put", "t_pre", "t_h2d_done", "t_dev_start",
               "t_ready", "late", "t_fetch", "h2d_bytes", "d2h_bytes",
               "trace_ids")


def _us(t: float) -> float:
    """Monotonic seconds → trace microseconds (one clock for every track:
    batch timeline stamps and span t0/finish are the same monotonic
    domain, so events line up without translation)."""
    return round(t * 1e6, 1)


def canvas_side(key) -> int:
    """THE decoder of the slab row-shape convention back to the canvas
    bucket's side length: yuv420 rows are (s·3/2, s), rgb rows (s, s, 3)
    — s is the last spatial axis in both layouts. Single definition,
    shared by the engine's econ cells, the batcher's padding counters,
    and the trace export's track naming, so a future wire-format change
    cannot silently misattribute canvas buckets in one of them."""
    try:
        return int(key[1] if len(key) == 2 else key[0])
    except Exception:
        return 0


def effective_window(requested_s: float | None,
                     retention_s: float | None,
                     default_s: float = 60.0,
                     max_s: float = 3600.0) -> float:
    """THE trace-window clamp: one place where the requested ``last_s``,
    the flight recorder's actual recent-ring retention, and the export
    cap meet. Before this existed /debug/trace clamped to a fixed 3600 s
    while the recent ring was entry/byte-capped independently, so a
    large ``last_s`` silently answered with whatever the ring happened
    to hold — now the caller reports the effective window back.

    ``retention_s`` is ``FlightRecorder.retention_s()``: None while the
    ring is empty (no clamp — the batch timelines still carry data for
    the full requested window), else the ring's oldest-entry age, floored
    at 1 s so a just-started ring never zeroes the window.
    """
    win = default_s if requested_s is None else max(1.0, float(requested_s))
    win = min(win, max_s)
    if retention_s is not None:
        win = min(win, max(1.0, retention_s))
    return round(win, 3)


def chrome_trace(models: list[dict], requests: list[tuple],
                 last_s: float | None = None,
                 now: float | None = None,
                 instants: list[dict] | None = None) -> dict:
    """Serialize batch timelines + finished request spans into Chrome-trace
    JSON (the ``chrome://tracing`` / Perfetto "JSON trace" dialect).

    ``models`` is ``[{"name": str, "timeline": batcher.batch_timeline()}]``
    — each model becomes one trace process whose threads are the pipeline
    stages: an ``assemble canvas=S`` track per canvas bucket (builder open
    → seal: the decode/commit window) and per-replica ``transfer``/
    ``execute``/``fetch`` tracks (launch → launched → done, and fetch
    thread's turn → done), beside the flight's ``copy`` (launch → the H2D
    copy landed) and ``device`` (the device's turn → outputs computed)
    tracks, where the engine stamped them. Bulk batches are tagged
    in the event name and args. ``requests`` is
    ``[(t0_mono, t_end_mono, span_dict)]`` (FlightRecorder.trace_records)
    — rendered as async events on a "requests" process so overlapping
    requests stack instead of fighting for one row. The decode(N+1) ∥
    execute(N) overlap bench asserts numerically is VISIBLE here: assemble
    bars of batch N+1 sit under execute bars of batch N on the same
    timebase.
    """
    if now is None:
        now = time.monotonic()
    cutoff = None if last_s is None else now - last_s
    events: list[dict] = []
    events.append({
        "ph": "M", "name": "process_name", "pid": 1, "tid": 0,
        "args": {"name": "requests"},
    })
    for pid0, m in enumerate(models):
        pid = pid0 + 2
        events.append({
            "ph": "M", "name": "process_name", "pid": pid, "tid": 0,
            "args": {"name": f"model {m.get('name') or 'default'}"},
        })
        for rec in m.get("timeline", ()):
            t_open, t_seal = rec.get("t_open"), rec.get("t_seal")
            t_launch, t_launched = rec.get("t_launch"), rec.get("t_launched")
            t_done = rec.get("t_done")
            end = t_done if t_done is not None else now
            if cutoff is not None and end < cutoff:
                continue
            bulk = bool(rec.get("bulk"))
            tag = "bulk " if bulk else ""
            s = canvas_side(rec.get("key") or ())
            r = rec.get("replica", 0)
            args = {
                "seq": rec.get("seq"), "rows": rec.get("rows"),
                "bucket": rec.get("bucket"), "replica": r,
                "class": "bulk" if bulk else "interactive",
                # Whatever else the record holds (the seal's reason, the
                # engine's t_put/t_pre, bytes each way, the trace IDs that
                # rode): the request events carry the batch seqs back.
                **{k: rec[k] for k in _BATCH_ARGS if rec.get(k) is not None},
            }
            legs = [
                (f"assemble canvas={s}", f"{tag}assemble b{rec.get('seq')}",
                 t_open, t_seal),
                (f"replica {r} transfer", f"{tag}transfer b{rec.get('seq')}",
                 t_launch, t_launched),
                (f"replica {r} execute", f"{tag}execute b{rec.get('seq')}",
                 t_launched, t_done),
                # The completion thread's own share of execute: from the
                # moment it turned to this batch to the outputs on the host.
                (f"replica {r} fetch", f"{tag}fetch b{rec.get('seq')}",
                 rec.get("t_fetch"), t_done),
                # The flight's own phases: the copy in flight, and the
                # device's work on the batch (the wait behind earlier calls
                # lies between them).
                (f"replica {r} copy", f"{tag}copy b{rec.get('seq')}",
                 t_launch if rec.get("t_h2d_done") is not None else None,
                 rec.get("t_h2d_done")),
                (f"replica {r} device", f"{tag}device b{rec.get('seq')}",
                 rec.get("t_dev_start"), rec.get("t_ready")),
            ]
            for tid, name, a, b in legs:
                if a is None:
                    continue
                b_eff = b if b is not None else now
                events.append({
                    "ph": "X", "cat": "batch", "name": name,
                    "pid": pid, "tid": tid,
                    "ts": _us(a), "dur": max(0.1, _us(b_eff) - _us(a)),
                    "args": args if b is not None
                    else {**args, "inflight": True},
                })
    for t0, t1, d in requests:
        if cutoff is not None and t1 < cutoff:
            continue
        meta = d.get("meta", {})
        name = d.get("class", "interactive") + " request"
        common = {
            "cat": "request", "id": d.get("trace_id"), "name": name,
            "pid": 1, "tid": 1,
        }
        events.append({
            **common, "ph": "b", "ts": _us(t0),
            "args": {
                "trace_id": d.get("trace_id"), "status": d.get("status"),
                "stages_ms": d.get("stages_ms", {}),
                **{k: meta[k] for k in ("model", "batches") if k in meta},
            },
        })
        events.append({**common, "ph": "e", "ts": _us(t1), "args": {}})
    # Telemetry events (hot-swaps, pressure transitions, chaos, SLO alert
    # fire/clear) as global instant events: the vertical line that makes a
    # p99 cliff line up visually with the swap that caused it.
    for ev in instants or ():
        t = ev.get("t")
        if t is None or (cutoff is not None and t < cutoff):
            continue
        events.append({
            "ph": "i", "s": "g", "cat": "telemetry",
            "name": ev.get("kind", "event"), "pid": 1, "tid": 0,
            "ts": _us(t),
            "args": {k: v for k, v in ev.items() if k not in ("t", "kind")},
        })
    events.sort(key=lambda e: e.get("ts", 0))
    return {
        "traceEvents": events,
        "displayTimeUnit": "ms",
        "otherData": {
            "clock": "monotonic",
            "window_s": last_s,
            "exported_at_mono": round(now, 6),
        },
    }
