"""Slot-leased dynamic request batcher with a pipelined dispatch path
(SURVEY.md §1.1 — the layer the reference lacks).

The reference serializes requests: one ``sess.run`` per HTTP request, so
throughput ≈ 1/latency (SURVEY.md §3.2). The first rework of this layer
queued decoded canvases and had ONE dispatcher thread copy each canvas
into a staging-slab row — correct, but it serialized all staging on that
thread and cost every image a second host copy (decode buffer → canvas →
slab). This version inverts the flow with **slot leasing**:

- A caller (serving/staging.py's ``stage_image``, on an HTTP worker or a
  job's decode thread — the one place that picks ``lease`` or
  ``lease_ragged`` for the wire) asks for a slot in the currently-open
  *batch builder* for its canvas row shape. The lease hands back a view
  of the slot's slab row, and the native decoder writes the JPEG
  **directly into it** — wire bytes → slab, one copy, staged in parallel
  across the worker pool with the GIL released.
- ``commit(hw)`` marks the slot ready; ``release()`` abandons it (decode
  failure, client error). A sealed batch pads abandoned/expired slots as
  hw=1×1 holes — the on-device resize reads one pixel and the row's
  output is dropped.
- Engines without the staging API (test fakes, embedders) get builders
  that collect (canvas, hw) pairs and dispatch via the legacy stacked
  path; ``submit()`` keeps the decoded-canvas entry point on top of the
  same lease machinery (one ``write_row`` copy into the slab).

**Pipelined dispatch** ("Optimizing Prediction Serving on Low-Latency
Serverless Dataflow", PAPERS.md — the request path as a dataflow of
overlappable stages). The earlier design ran seal → device_put → execute
→ fetch in lockstep: ONE sealer thread performed the host→device
transfer inline (serializing every batch's transfer behind the previous
one's) and ONE fetcher thread fetched and resolved batches serially.
Now each stage owns its own thread(s) and batches flow through them like
a CPU pipeline:

    HTTP workers      decode/commit into builder N+1's slab   (parallel)
    sealer            ONLY seals: picks a ready builder, hands it off
    launch pool       device_put + execute enqueue + async D2H start
                      (transfers of consecutive batches overlap — on
                      BDP-limited links concurrent streams multiply
                      effective bandwidth)
    device            executes batch N while N+1 transfers and N+2
                      assembles
    completion pool   blocks on outputs, resolves futures; postprocess/
                      serialize then run on the awaiting HTTP workers

On a ragged arena that ships by pages (a replica of one device,
engine.RaggedSlab), the copy starts before the seal: each slot that leaves
PENDING settles, and every page it completes goes to the engine's shipper
thread at once (``_settle_locked``), so the launch copies only the tail.

``pipeline_depth`` bounds dispatched-but-unfetched batches PER canvas
bucket (sealed batches of one row shape can't starve another's), and the
sealer blocks on the condition variable at the cap — a bucket's one open
builder keeps growing exactly when the device is the bottleneck. An
engine may also state a ceiling on calls in flight over ALL buckets
(``max_calls_in_flight``: what its device's memory holds beside the
weights). Under it a builder past its window seals only into a call slot
that is free and not already promised to a sealed batch, oldest builder
first; the others keep accepting until they are full or a slot is free
for them (``window_holds_total`` counts the sealer passes that held one).
Sealing them all when one slot frees would leave the rest sealed, small
and waiting, while their canvases' new arrivals open fresh builders. A
builder past its window never waits behind a batch opened after it: it
takes the next slot, and sealed batches dispatch oldest first. Every batch's
lifecycle is stamped into a small ring (``batch_timeline``): builder
open, seal, launch start/end, fetch done — the record bench.py's
``pipeline`` block and the overlap tests read to PROVE decode of batch
N+1 overlapped execute of batch N.

**Placement-aware routing** (serving/placement.py): engines whose
placement replicates the model across device groups expose
``num_replicas``/``replica_loads``, and the sealer routes each sealed
batch to one replica — round-robin order, overridden toward the replica
with the fewest in-flight dispatches — at the moment it takes its
pipeline-depth slot. Depth is gated per (canvas bucket, replica), so N
replicas sustain N × ``pipeline_depth`` batches in flight and each
replica keeps its own transfer∥execute overlap. The chosen replica rides
the timeline record (per-chip busy analysis) and the batch's spans.

Batch-delay policy: ``max_delay_ms`` is a CAP, not a constant. Each
builder's assembly window adapts to pressure — it shrinks toward 0 when
no slots are outstanding (an idle device should never sit waiting for
company that isn't coming) and grows toward the cap under backlog (when
the device is the bottleneck, waiting buys bigger batches for free).
``current_delay_ms`` exposes the live value; ``/stats`` reports it.

Backpressure has two regimes: with ``max_queue == 0`` (default) the
lease path *blocks* at the outstanding-slot cap (``max_batch × max(2,
pipeline_depth)`` — the ``lease_wait`` span stage), bounding host memory
under overload. With ``max_queue > 0`` a backlog at or above that many
images **fails fast** instead: ``lease()`` raises :class:`BacklogFull`
(HTTP maps it to 503 + ``Retry-After``) so overload sheds in
microseconds instead of queueing toward the request timeout — the
down-payment on admission control (ROADMAP item 3).

**Bulk traffic class** (serving/jobs.py, ISSUE 10): ``lease(...,
bulk=True)`` / ``submit(..., bulk=True)`` stage into SEPARATE builders
that assemble up to ``bulk_max_batch`` rows (the throughput-mode
operating point: min(jobs_batch, top compiled bucket)) and are strictly
lower priority than interactive traffic: a sealed bulk batch takes a
device slot only when (1) no interactive batch is sealed and waiting to
dispatch, (2) the interactive pipeline is IDLE — zero interactive
batches in flight, so an interactive batch sealed during a bulk execute
always runs before the next bulk batch — and (3) bulk's own in-flight
cap (``bulk_inflight``, the ``--jobs-max-inflight`` knob) has room —
the bound on how much device time a background job may hold at once,
which is what keeps interactive p99 within one bulk batch of its idle
value. An anti-starvation valve (``bulk_starvation_s``) admits one bulk
batch after a window of continuous gating, so closed-loop interactive
saturation degrades a job to slow, never to zero.
Bulk backpressure always *blocks* (the job runner is the only client and
can wait); it is invisible to the interactive regime: bulk slots count
in neither ``max_queue`` rejection, the interactive slot cap, nor the
adaptive-delay controller's depth input. While the gate is closed a
past-deadline bulk builder keeps ACCEPTING leases — bulk batches grow
toward capacity exactly while interactive load holds the device, so the
job pays the interactive burst back in batch efficiency.

All deadline/latency arithmetic uses ``time.monotonic()`` — a wall-clock
step (NTP slew, manual set) must never stretch or collapse the batching
window or corrupt recorded latencies.

Concurrency model (SURVEY.md §5.2): builder bookkeeping lives under ONE
condition variable; slab *rows* are written lock-free because every slot
has exactly one lessee and a slot is only dispatched after its lease
resolved. JAX calls happen on the launch threads (jit dispatch is
thread-safe; each slab is owned by exactly one in-flight batch). A
force-expired lease's thread may still be decoding into its row while
the batch runs — harmless by construction: the row is padded hw=1×1, its
future already failed, and the slab cannot return to the pool until that
thread drops its lease (engine.StagingSlab refcount).

Failure isolation (SURVEY.md §5.3): a failed batch fails only its
requests' futures, never the process; per-request timeouts are enforced
at the caller.
"""

from __future__ import annotations

import logging
import math
import queue
import threading
import time
from collections import deque
from concurrent.futures import Future

import numpy as np

from ..utils.locks import named_condition
from ..utils.metrics import RollingStats
from ..utils.tracing import canvas_side, stage
from .chaos import ChaosError
from .overload import DEFAULT_TENANT, DeadlineExceeded, QuotaExceeded

log = logging.getLogger("tpu_serve.batcher")

# Why a builder sealed (rec["reason"], lifecycle.by_reason): every slot
# leased; the ragged arena out of bytes for the next image; the batch window
# over with a dispatch slot free for it; flush_bulk(); shutdown's drain.
SEAL_REASONS = ("full", "arena", "window", "flush", "drain")

# Slot-lease states. PENDING: lessee still decoding. READY: committed, row
# valid. HOLE: abandoned (released, expired, or shutdown) — padded at seal.
_PENDING, _READY, _HOLE = 0, 1, 2


class ShuttingDown(RuntimeError):
    """Request rejected because the batcher is draining for shutdown.
    The HTTP layer maps this to 503 (the standard load-balancer draining
    signal), never 500."""


class BacklogFull(RuntimeError):
    """Request rejected because the batcher's backlog is at ``max_queue``
    images: with a bounded queue the honest overload answer is an
    immediate 503 + Retry-After (the HTTP layer adds the header from
    ``retry_after_s``), not a silent wait toward the request timeout."""

    def __init__(self, msg: str, retry_after_s: float = 1.0):
        super().__init__(msg)
        self.retry_after_s = retry_after_s


class LeaseExpired(RuntimeError):
    """A leased slot was not committed or released within the lease
    timeout; its batch dispatched without it (the slot became a hole)."""


class SlotLease:
    """One reserved row in an assembling batch.

    ``row`` is a live numpy view of the slot's slab canvas row (None for
    engines without slot-lease slabs) — decode straight into it, then
    ``commit(hw)``. ``commit(hw, canvas=...)`` instead copies a decoded
    canvas into the slot (the PIL-fallback / ``submit()`` path). Exactly
    one of commit/release must be called; the result arrives on
    ``future``.
    """

    __slots__ = ("_batcher", "builder", "index", "future", "span", "hw",
                 "canvas", "state", "leased_at", "committed_at", "row",
                 "slab_held", "deadline", "tenant")

    def __init__(self, batcher, builder, index: int, span,
                 deadline: float | None = None, tenant: str | None = None):
        self._batcher = batcher
        self.builder = builder
        self.index = index
        self.future: Future = Future()
        self.span = span
        self.hw = None
        self.canvas = None
        self.state = _PENDING
        self.leased_at = time.monotonic()
        self.committed_at: float | None = None
        self.row = None
        self.slab_held = False
        # Absolute monotonic deadline (None = no SLO): the sealer re-checks
        # it at seal time so a batch never ships an already-dead row.
        self.deadline = deadline
        self.tenant = tenant

    def commit(self, hw, canvas=None) -> Future:
        return self._batcher._commit(self, hw, canvas)

    def release(self) -> None:
        self._batcher._release_lease(self)


class _Builder:
    """One assembling batch for a single canvas row shape: a slab (or a
    plain slot list for engines without the staging API) plus its leases
    and sealing deadline."""

    __slots__ = ("key", "slab", "capacity", "leases", "opened_at", "deadline",
                 "accepting", "dispatched", "n_pending", "n_ready", "n_holes",
                 "replica", "bulk", "tenant", "reason", "seq")

    def __init__(self, key, slab, capacity: int, deadline: float,
                 bulk: bool = False):
        self.key = key
        self.bulk = bulk
        # Bulk builders carry the tenant of the job staging into them
        # (set by the first lease): the bulk gate charges that tenant's
        # quota at dispatch. Interactive builders mix tenants per slot.
        self.tenant: str | None = None
        self.slab = slab
        self.capacity = capacity
        self.leases: list[SlotLease] = []
        self.opened_at = time.monotonic()
        self.deadline = deadline
        self.accepting = True
        # Why the builder stopped accepting (SEAL_REASONS), set once by
        # _close_builder_locked; rides the batch record and the
        # per-reason lifecycle counters.
        self.reason: str | None = None
        self.dispatched = False
        self.n_pending = 0
        self.n_ready = 0
        self.n_holes = 0
        # Dispatch replica, assigned by the sealer's routing decision the
        # moment the batch takes its pipeline-depth slot (0 for engines
        # without replica routing).
        self.replica = 0
        # The batch's seq, taken when its first arena page is handed to the
        # engine's shipper (so the early copies' annotations carry it), else
        # at hand-off.
        self.seq: int | None = None


class Batcher:
    def __init__(self, engine, max_batch: int = 32, max_delay_ms: float = 2.0,
                 stats: RollingStats | None = None, max_in_flight: int = 4,
                 adaptive_delay: bool = True, lease_timeout_s: float = 10.0,
                 name: str = "", pipeline_depth: int | None = None,
                 max_queue: int = 0, transfer_threads: int | None = None,
                 completion_threads: int | None = None,
                 bulk_max_batch: int | None = None, bulk_inflight: int = 2,
                 bulk_max_delay_ms: float = 1000.0,
                 bulk_starvation_s: float = 2.0,
                 admission=None, chaos=None):
        self.engine = engine
        # Overload control (serving/overload.py): the shared per-tenant
        # token-bucket admission layer (None = no quota enforcement) and
        # the chaos fault injector (None = no injection). Both are
        # registry-owned and shared across every model's batcher.
        self.admission = admission
        self.chaos = chaos
        # Model name under a multi-model registry: names the threads (one
        # sealer + launch/completion pool PER model — per-model builders are
        # what keeps one model's queue from starving another) and labels
        # telemetry.
        self.name = name
        # Never assemble more than the engine's top compiled batch shape —
        # dispatch refuses larger batches at request time, so enforcing the
        # invariant here (not just at server.py's call site) keeps every
        # embedder/test constructor safe.
        self.max_batch = min(max_batch, getattr(engine, "max_batch", max_batch))
        self.max_delay_s = max_delay_ms / 1e3
        self.adaptive_delay = adaptive_delay
        # Live assembly window in [0, max_delay_s]; EMA over outstanding
        # slots. Starts at 0: the first request after an idle period
        # dispatches immediately instead of paying the full cap.
        self._delay_s = 0.0 if adaptive_delay else self.max_delay_s
        self.lease_timeout_s = lease_timeout_s
        self.stats = stats or RollingStats()
        # Dispatched-but-unfetched batches allowed PER canvas-bucket key.
        # ``max_in_flight`` is the legacy name for the same knob; an explicit
        # ``pipeline_depth`` wins.
        self.pipeline_depth = max(
            1, pipeline_depth if pipeline_depth is not None else max_in_flight
        )
        # Backlog bound in images: 0 = block at the outstanding-slot cap
        # (classic backpressure); > 0 = lease() fails fast with BacklogFull
        # once the leased-undispatched backlog reaches it.
        self.max_queue = max(0, int(max_queue))
        # Bulk traffic class (jobs): batch target for bulk builders —
        # capped at the engine's TOP COMPILED BUCKET (batch_buckets[-1]),
        # NOT engine.max_batch: max_batch is the interactive request cap
        # (often far below the throughput bucket — the whole point of the
        # bulk class is running the big compiled shape the interactive
        # path never uses) — plus the in-flight batch cap (how much
        # device time a job may hold at once) and the bulk assembly
        # window (a CAP like max_delay_ms; bulk is throughput traffic, so
        # it is much wider and non-adaptive — a padded 256-bucket execute
        # costs the same as a full one, so sealing early to save a
        # fraction of a second burns whole-batch device time; full chunks
        # seal at capacity, and the job runner seals the manifest tail
        # explicitly via flush_bulk(), so the deadline is only the
        # backstop for a staging client that died mid-chunk).
        want = bulk_max_batch if bulk_max_batch is not None else 256
        buckets = getattr(engine, "batch_buckets", None)
        top = (buckets[-1] if buckets
               else getattr(engine, "max_batch", want))
        self.bulk_max_batch = max(1, min(want, top))
        self.bulk_inflight_cap = max(1, int(bulk_inflight))
        self.bulk_delay_s = max(0.0, bulk_max_delay_ms) / 1e3
        # Anti-starvation valve: strict priority must not become zero
        # progress — under SUSTAINED interactive load (closed-loop
        # clients keep the pipeline permanently non-idle) a ready bulk
        # batch gated for this long is admitted once, then the clock
        # re-arms. Saturated floor: one bulk batch per window; the
        # amortized interactive-tail cost is one execute quantum per
        # window.
        self.bulk_starvation_s = max(0.05, float(bulk_starvation_s))
        self._bulk_gated_since: float | None = None
        self._bulk_starvation_total = 0
        self._staged = hasattr(engine, "acquire_staging")
        # The real engine stamps its own span stages and takes the batch's
        # record (``rec=``): it names its profiler annotations by
        # rec["seq"] and writes t_put/t_pre/h2d_bytes/d2h_bytes into it.
        # Fakes and embedders with the plain signatures never see either
        # keyword.
        self._engine_takes_rec = getattr(engine, "supports_span_tracing", False)
        # Decode-into-slab is offered to callers (staging.py) only when the
        # engine's slabs speak the slot-lease API; otherwise submit() is
        # the entry point and staging is write_row/stack at seal time.
        self.supports_lease = self._staged and getattr(
            engine, "supports_slot_lease", False
        )
        # Ragged packing (ROADMAP item 5): when the engine serves the
        # ragged wire, lease_ragged() stages TIGHT decoded bytes into flat
        # per-batch arenas (engine.RaggedSlab) instead of padded canvas
        # rows, and _launch dispatches them via engine.dispatch_ragged.
        # The classic lease()/submit() paths stay fully functional next to
        # it (their builders key differently), so embedders and the
        # decoded-canvas entry point are unchanged.
        self.ragged = bool(
            self._staged
            and getattr(engine, "ragged", False)
            and hasattr(engine, "acquire_ragged")
            and hasattr(engine, "dispatch_ragged")
        )
        # Placement-aware routing: engines with replicas (engine.placement)
        # get each sealed batch routed to one replica's dispatch stream —
        # round-robin order with a least-loaded override (the engine's
        # in-flight dispatch count per replica) — and pipeline depth is
        # gated PER (canvas bucket, replica), so N replicas sustain up to
        # N × pipeline_depth batches in flight. Fakes/embedders without the
        # routing API keep the single-stream behavior bit-for-bit.
        self._route = getattr(engine, "supports_replica_routing", False)
        self._n_replicas = max(1, getattr(engine, "num_replicas", 1))
        self._rr = 0  # round-robin cursor over replicas
        # Launch/completion pools sized to the placement (None = auto):
        # every replica can have a transfer in flight and a fetch blocking
        # at once, so 2 threads — the single-stream default — would
        # serialize an 8-replica placement back to 2-wide (measured: 232
        # vs 360 img/s on the 8-replica CPU mesh). Explicit values win.
        if transfer_threads is None:
            transfer_threads = max(2, min(16, self._n_replicas))
        if completion_threads is None:
            completion_threads = max(2, min(16, self._n_replicas))
        self._cond = named_condition("batcher.cond")
        # Accepting builders by (row-shape key, bulk flag): the bulk
        # traffic class assembles in its own builders so a job's images
        # never ride (or delay) an interactive batch.
        self._open: dict[tuple, _Builder] = {}
        self._closing: list[_Builder] = []  # sealed to new leases, undispatched
        # Leased-but-undispatched INTERACTIVE slots (pending + ready). The
        # backpressure signal: lease() blocks (or rejects) at the cap, and
        # the adaptive window's depth input. Bulk slots are counted apart
        # (_bulk_pending) so a job's backlog can never trip the
        # interactive 503 path or stretch the interactive batch window.
        self._pending_slots = 0
        self._bulk_pending = 0
        self._bulk_inflight = 0
        self._bulk_sealed_total = 0
        self._bulk_images_total = 0
        self._bulk_gate_holds = 0  # sealer wakeups with a gated-ready bulk batch
        self._max_pending = self.max_batch * max(2, self.pipeline_depth)
        if self.max_queue:
            # A bounded queue is authoritative: if it is LARGER than the
            # blocking slot cap, raise the cap so the backlog can actually
            # reach the bound and reject (otherwise lease() would block at
            # the cap and the 503 path would be dead code); if SMALLER,
            # rejection fires first and the cap never binds.
            self._max_pending = max(self._max_pending, self.max_queue)
        # Pipeline accounting: batches sealed-and-handed-off but not yet
        # fetched, per (canvas-bucket key, replica). The sealer blocks at
        # pipeline_depth per entry (woken by completion when a fetch
        # lands); with N replicas a bucket sustains N × depth in flight.
        self._inflight_by_key: dict[tuple, int] = {}
        self._inflight_total = 0
        self._inflight_peak = 0
        # Sealed builders → launch pool → dispatched handles → completion
        # pool. Unbounded queues: depth gating happens at the seal decision,
        # so nothing downstream can block a stop() sentinel.
        self._launch_q: queue.Queue = queue.Queue()
        self._done_q: queue.Queue = queue.Queue()
        self._running = False
        suffix = f"[{name}]" if name else ""
        self._sealer = threading.Thread(
            target=self._seal_loop, name=f"batch-sealer{suffix}", daemon=True
        )
        self._launchers = [
            threading.Thread(target=self._launch_loop,
                             name=f"batch-launch-{i}{suffix}", daemon=True)
            for i in range(max(1, transfer_threads))
        ]
        self._completions = [
            threading.Thread(target=self._fetch_loop,
                             name=f"batch-complete-{i}{suffix}", daemon=True)
            for i in range(max(1, completion_threads))
        ]
        # Legacy handle kept for tests/embedders that join "the fetcher".
        self._fetcher = self._completions[0]
        # Lease/builder telemetry for /stats and /metrics.
        self._sealed_total = 0
        self._lease_timeouts_total = 0
        self._holes_total = 0
        self._rejects_total = 0
        # Overload-shed accounting (ISSUE 13): deadline sheds split by
        # WHERE they fired — lease-time (admission predicted a miss; no
        # decode or device time spent) vs seal-time (the deadline passed
        # while the row waited; decode spent, device time saved).
        self._deadline_sheds_total = 0
        self._deadline_seal_sheds_total = 0
        self._quota_sheds_total = 0
        self._bulk_quota_holds = 0  # bulk gate closed on tenant quota
        # Per-batch lifecycle ring (open/seal/launch/done monotonic stamps):
        # the overlap evidence bench.py's ``pipeline`` block and the
        # decode(N+1)∥execute(N) tests read.
        self._batch_seq = 0
        self._timeline: deque = deque(maxlen=512)
        # Cumulative lifecycle counters (/stats → batcher.lifecycle), all
        # monotonic, all updated under self._cond: _hand_off and _batch_done
        # take it anyway, _launch takes it once more for its stamp. A
        # batch's phases (open → seal → launch → done) sum to t_done -
        # t_open; inflight is t_launched → t_done. Where the engine stamps
        # the batch's flight (engine.Flight), [t_launch, t_done] splits
        # into four more that tile it: the H2D copy (→ t_h2d_done), the
        # wait behind earlier calls on the device (→ t_dev_start), the
        # device's own work (→ t_ready) and the D2H (→ t_done); a failed
        # dispatch or fetch adds to none of them. The starved clock runs
        # while no batch stands between its t_launch and its t_done on any
        # replica: stamped when that count goes 1→0, added when it goes 0→1
        # (exact to the time a completion thread takes from stamping t_done
        # to _batch_done's lock). The h2d-bound clock runs while some
        # batch's copy is in flight and no batch is in its device phase.
        self._life = {
            "batches_total": 0,
            "by_reason": dict.fromkeys(SEAL_REASONS, 0),
            # sealer passes in which a builder past its window, nothing
            # decoding, stayed open: the engine's ceiling had no call slot
            # free for it (_pick_action_locked)
            "window_holds_total": 0,
            "open_s_total": 0.0, "launch_wait_s_total": 0.0,
            "inflight_s_total": 0.0,
            "h2d_s_total": 0.0, "device_queue_s_total": 0.0,
            "device_s_total": 0.0, "d2h_s_total": 0.0,
            "h2d_bound_s_total": 0.0, "stamps_late_total": 0,
            "h2d_bytes_total": 0, "d2h_bytes_total": 0,
            # a ragged arena shipped by pages: the prefix's pages, and the
            # pages and bytes whose copy started before the batch's t_launch
            "h2d_early_bytes_total": 0, "h2d_pages_early_total": 0,
            "h2d_pages_total": 0,
            "unpack_kernel_batches_total": 0,
            "starved_s_total": 0.0,
            # what the engine's model counts a call (a token decoder's
            # tokens, picks and steps), summed in _batch_done
            **{f"{name}_total": 0.0 for name in getattr(engine, "counter_names", ())},
        }
        self._launched_now = 0
        self._starved_since = time.monotonic()
        # The h2d-bound clock, from the phases of batches that are done:
        # t_launch of each batch launched and not done (nothing can begin
        # before the earliest of them), the copy and device intervals not
        # yet swept, and how far the clock has been swept.
        self._launched: dict[int, float] = {}
        self._phases: list[tuple[float, float, bool]] = []
        self._phases_swept = self._starved_since
        # Padding-waste accounting per (canvas bucket, batch bucket):
        # [batches, rows real, rows dispatched, real px (Σ h·w of committed
        # rows), canvas px (batch bucket × canvas²)]. Two waste axes: row
        # padding (small batches run at the compiled bucket — wasted model
        # FLOPs) and canvas padding (images smaller than their canvas ship
        # and resize dead pixels — wasted wire bytes + preprocess FLOPs).
        # Bounded by the compiled bucket grid; exported via builder_stats
        # → /stats "economics" and the /metrics padding counters
        # (ROADMAP item 5: "measure it first").
        self._padding: dict[tuple[int, int], list] = {}

    def start(self):
        self._running = True
        self._sealer.start()
        for t in self._launchers:
            t.start()
        for t in self._completions:
            t.start()

    def stop(self):
        with self._cond:
            self._running = False
            self._cond.notify_all()
        # The sealer drains every undispatched builder (drain-grace-bounded
        # wait for in-flight decodes) before exiting — the drain guarantee.
        # Sentinels go in AFTER each upstream stage joined: the queues are
        # FIFO, so every handed-off builder is launched before a launcher
        # exits, and every launched batch is fetched before a completion
        # thread exits.
        self._sealer.join(timeout=5)
        for _ in self._launchers:
            self._launch_q.put(None)
        for t in self._launchers:
            t.join(timeout=5)
            if t.is_alive():
                log.warning(
                    "launch thread wedged at shutdown (device_put stalled?); "
                    "its batch's futures will be failed, not fetched"
                )
        for _ in self._completions:
            self._done_q.put(None)
        for t in self._completions:
            t.join(timeout=5)
        # Drain contract: every submitted request's future must resolve.
        # Anything still sitting in the queues (a wedged launcher that
        # handed off after the sentinels, a completion join that timed
        # out) would otherwise hang its callers until their request
        # timeout — fail those futures now.
        for q_ in (self._launch_q, self._done_q):
            while True:
                try:
                    item = q_.get_nowait()
                except queue.Empty:
                    break
                if item is None:
                    continue
                if q_ is self._launch_q:
                    b, ready, _rec = item
                    self._fail(ready, ShuttingDown("server shutting down"))
                    self._recycle(b)
                else:
                    ready, _idxs, _handle, _rec = item
                    self._fail(ready, ShuttingDown("server shutting down"))

    # --------------------------------------------------------------- leasing

    def _retry_after_locked(self) -> float:
        """Honest Retry-After estimate for a rejected request: backlog ÷
        recent drain rate, clamped to [1, 30] s. O(1) — the reject path
        runs under overload and must never sort a stats window."""
        rate = self.stats.rate_hint()
        if rate <= 0:
            return 1.0
        return min(30.0, max(1.0, math.ceil(self._pending_slots / rate)))

    def _expected_wait_locked(self) -> float:
        """Deadline-admission estimate: time a slot leased NOW waits
        before its result lands — backlog ÷ recent drain rate + the live
        assembly window + a device-time EMA. O(1) (rate_hint/device_hint
        never sort; batcher.cond → stats.lock is the declared climb):
        the check runs on every deadline-carrying lease under exactly
        the load that makes it matter. Cold start (no rate yet) counts
        only the window — never shed on a guess of zero evidence."""
        backlog_s = 0.0
        rate = self.stats.rate_hint()
        if rate > 0:
            backlog_s = self._pending_slots / rate
        return backlog_s + self._delay_s + self.stats.device_hint()

    def _admit_locked(self, t0: float, bulk: bool, deadline, tenant):
        """Shared admission for :meth:`lease` / :meth:`lease_ragged` —
        shed order backlog → quota → deadline, then the blocking
        outstanding-slot cap. Must run under the condition."""
        if bulk:
            # Bulk always blocks (the job runner can wait; rejection
            # would just make it retry): cap = a staged batch per
            # allowed in-flight batch plus one assembling.
            cap = self.bulk_max_batch * (self.bulk_inflight_cap + 1)
            while self._running and self._bulk_pending >= cap:
                self._cond.wait(timeout=0.25)
        else:
            if (self.max_queue and self._running
                    and self._pending_slots >= self.max_queue):
                self._rejects_total += 1
                raise BacklogFull(
                    f"batcher backlog {self._pending_slots} images ≥ "
                    f"max_queue {self.max_queue}",
                    retry_after_s=self._retry_after_locked(),
                )
            if (self.admission is not None and self._running
                    and not self.admission.try_charge(tenant)):
                self._quota_sheds_total += 1
                raise QuotaExceeded(
                    f"tenant {tenant or DEFAULT_TENANT!r} quota "
                    f"exhausted",
                    tenant=tenant or DEFAULT_TENANT,
                    retry_after_s=self.admission.retry_after(tenant),
                )
            if (deadline is not None and self._running
                    and self._pending_slots > 0):
                # Backlog-gated: with zero pending slots the estimate
                # is all device-EMA, and a cold start's compile time
                # seeds that EMA seconds high — shedding an idle
                # server on a stale estimate would turn every
                # post-compile request into a spurious 504. Real
                # overload always has a backlog.
                wait = self._expected_wait_locked()
                if t0 + wait > deadline:
                    self._deadline_sheds_total += 1
                    raise DeadlineExceeded(
                        f"deadline in {max(0.0, deadline - t0) * 1e3:.0f}"
                        f" ms but expected wait is {wait * 1e3:.0f} ms",
                        expected_wait_s=wait,
                        retry_after_s=self._retry_after_locked(),
                    )
            while self._running and self._pending_slots >= self._max_pending:
                self._cond.wait(timeout=0.25)
        if not self._running:
            raise ShuttingDown("server shutting down")

    def lease(self, row_shape, span=None, bulk: bool = False,
              deadline: float | None = None,
              tenant: str | None = None) -> SlotLease:
        """Reserve a slot in the open builder for ``row_shape`` (opening one
        if needed). With ``max_queue`` set, a backlog at the cap rejects
        immediately with :class:`BacklogFull`; otherwise blocks only when
        the outstanding-slot cap is hit — that wait is stamped as the
        ``lease_wait`` span stage. ``bulk=True`` stages into the
        lower-priority bulk traffic class instead: its own builders
        (capacity ``bulk_max_batch``), its own blocking backpressure cap,
        never a :class:`BacklogFull`. Raises :class:`ShuttingDown` while
        draining.

        Overload admission (ISSUE 13) runs here, before any decode or
        device time is spent: a dry tenant token bucket raises
        :class:`QuotaExceeded` (429), and a ``deadline`` (absolute
        monotonic) the expected wait cannot meet raises
        :class:`DeadlineExceeded` (504) — shed order is backlog → quota
        → deadline, so a quota-violating tenant is charged nothing for
        requests the global backlog would have shed anyway. Bulk leases
        never shed (the job runner waits); their tenant rides the
        builder and is charged at the bulk gate's dispatch decision."""
        key = tuple(int(d) for d in row_shape)
        with stage(span, "lease_wait") as waited, self._cond:
            self._admit_locked(waited.t0, bulk, deadline, tenant)
            b = self._open.get((key, bulk))
            if b is None:
                b = self._new_builder_locked(key, bulk)
            if bulk and b.tenant is None and tenant is not None:
                b.tenant = tenant
            lease = SlotLease(self, b, len(b.leases), span,
                              deadline=deadline, tenant=tenant)
            b.leases.append(lease)
            b.n_pending += 1
            if bulk:
                self._bulk_pending += 1
            else:
                self._pending_slots += 1
            if b.slab is not None and hasattr(b.slab, "add_lease"):
                b.slab.add_lease()
                lease.slab_held = True
            if b.slab is not None and hasattr(b.slab, "row"):
                lease.row = b.slab.row(lease.index)
            if len(b.leases) >= b.capacity:
                self._close_builder_locked(b, "full")
            self._cond.notify_all()  # sealer: new deadline / full builder
        self.stats.record_lease_wait(waited.t1 - waited.t0)
        return lease

    def lease_ragged(self, need_bytes: int, canvas_s: int, span=None,
                     bulk: bool = False, deadline: float | None = None,
                     tenant: str | None = None) -> SlotLease:
        """Reserve ``need_bytes`` of tight arena space (one image at its
        native decoded stride, h·w·3 bytes) in the open RAGGED builder for
        canvas bucket ``canvas_s``. The lease's ``row`` is the flat byte
        view to decode into; ``commit(hw)`` stamps the image's decoded
        size (``commit(hw, canvas=img)`` instead copies a decoded RGB
        array tight — the PIL-fallback path). Size-aware packing happens
        here: an arena that cannot fit the image (out of bytes or slots)
        seals immediately and a fresh one opens, so small images pack many
        per canvas row while large ones still get full batches. Admission
        (backlog/quota/deadline sheds, the blocking slot cap) is identical
        to :meth:`lease`."""
        with stage(span, "lease_wait") as waited, self._cond:
            self._admit_locked(waited.t0, bulk, deadline, tenant)
            key = ("ragged", int(canvas_s))
            row_bytes = int(canvas_s) * int(canvas_s) * 3
            if need_bytes > row_bytes:
                # The staging plan bounds decoded dims by the canvas bucket,
                # so this is a caller bug, not a traffic condition.
                raise ValueError(
                    f"ragged lease of {need_bytes} B exceeds one "
                    f"{canvas_s}px canvas row ({row_bytes} B)"
                )
            b = self._open.get((key, bulk))
            if b is None:
                b = self._new_ragged_builder_locked(key, canvas_s, bulk)
            got = b.slab.alloc(need_bytes)
            if got is None:
                # Out of bytes or slots: this batch is as packed as it
                # gets — seal it now and start the next arena. (A fresh
                # arena always fits: need ≤ row_bytes ≤ arena_bytes.)
                self._close_builder_locked(b, "arena")
                self._cond.notify_all()
                b = self._new_ragged_builder_locked(key, canvas_s, bulk)
                got = b.slab.alloc(need_bytes)
            idx, view = got
            if bulk and b.tenant is None and tenant is not None:
                b.tenant = tenant
            lease = SlotLease(self, b, idx, span,
                              deadline=deadline, tenant=tenant)
            b.leases.append(lease)
            b.n_pending += 1
            if bulk:
                self._bulk_pending += 1
            else:
                self._pending_slots += 1
            b.slab.add_lease()
            lease.slab_held = True
            lease.row = view
            if b.slab.slots >= b.capacity:
                self._close_builder_locked(b, "full")
            self._cond.notify_all()  # sealer: new deadline / full builder
        self.stats.record_lease_wait(waited.t1 - waited.t0)
        return lease

    def submit(self, canvas: np.ndarray, hw: tuple[int, int], span=None,
               bulk: bool = False, deadline: float | None = None,
               tenant: str | None = None) -> Future:
        """Decoded-canvas entry point (tests, embedders, non-JPEG fallback):
        lease a slot and commit the canvas into it — one ``write_row`` copy
        on the caller's thread, batching identical to the lease path.
        :class:`BacklogFull` (and the overload sheds: QuotaExceeded,
        DeadlineExceeded) propagate to the caller (the HTTP layer owns
        the status + Retry-After mapping); ``bulk=True`` rides the bulk
        traffic class instead (blocks, never rejects)."""
        try:
            lease = self.lease(tuple(np.asarray(canvas).shape), span=span,
                               bulk=bulk, deadline=deadline, tenant=tenant)
        except ShuttingDown as e:
            # Fail fast during shutdown instead of stranding the caller
            # on a future nobody will resolve.
            f: Future = Future()
            f.set_exception(e)
            return f
        return lease.commit(hw, canvas=canvas)

    def _new_ragged_builder_locked(self, key, canvas_s: int,
                                   bulk: bool = False) -> _Builder:
        """Open a ragged builder: a flat byte arena (engine.RaggedSlab)
        whose dual capacity — slot count AND arena bytes — is what makes
        the packing size-aware (lease_ragged seals on whichever runs out
        first)."""
        capacity = self._capacity(canvas_s, bulk)
        slab = self.engine.acquire_ragged(capacity, canvas_s)
        capacity = min(capacity, slab.bucket)
        delay = self.bulk_delay_s if bulk else self._update_delay()
        b = _Builder(key, slab, capacity, time.monotonic() + delay, bulk=bulk)
        self._open[(key, bulk)] = b
        return b

    def _capacity(self, canvas_s: int, bulk: bool) -> int:
        """Rows one builder of this canvas bucket may hold: ``max_batch``
        (``bulk_max_batch`` for a job's), and no more than the engine allows
        at this canvas. A token decoder's ceiling is in token slots, rows
        times the canvas's tokens, which its model states
        (``InferenceEngine.max_rows``): 16 rows of a small canvas, 4 of a
        large one."""
        capacity = self.bulk_max_batch if bulk else self.max_batch
        max_rows = getattr(self.engine, "max_rows", None)
        return min(capacity, max_rows(canvas_s)) if max_rows else capacity

    def _new_builder_locked(self, key, bulk: bool = False) -> _Builder:
        capacity = self._capacity(canvas_side(key), bulk)
        slab = None
        if self._staged:
            # Top-capacity slab acquired up front (the final batch size is
            # unknown while slots lease); dispatch re-buckets to the
            # compiled shape covering the real row count.
            slab = self.engine.acquire_staging(capacity, key)
            capacity = min(capacity, getattr(slab, "bucket", capacity))
        delay = self.bulk_delay_s if bulk else self._update_delay()
        b = _Builder(key, slab, capacity, time.monotonic() + delay, bulk=bulk)
        self._open[(key, bulk)] = b
        return b

    def _close_builder_locked(self, b: _Builder, reason: str):
        if b.accepting:
            b.accepting = False
            b.reason = reason
            if self._open.get((b.key, b.bulk)) is b:
                del self._open[(b.key, b.bulk)]
            self._closing.append(b)

    def _dec_pending_locked(self, b: _Builder, n: int = 1):
        if b.bulk:
            self._bulk_pending -= n
        else:
            self._pending_slots -= n

    def _commit(self, lease: SlotLease, hw, canvas=None) -> Future:
        b = lease.builder
        # The slot write happens OUTSIDE the lock (it may be a full canvas
        # copy); the slot is exclusively this lessee's until commit.
        with stage(lease.span, "staging_write"):
            if canvas is not None:
                if b.slab is not None:
                    if getattr(b.slab, "is_ragged", False):
                        # PIL-fallback path on the ragged wire: the decoded
                        # RGB array copies TIGHT into the leased byte span
                        # (its size was the lease's need_bytes), then the
                        # meta commit.
                        lease.row[:] = np.ascontiguousarray(
                            canvas, dtype=np.uint8).reshape(-1)
                        b.slab.write_hw(lease.index, hw)
                    else:
                        b.slab.write_row(lease.index, canvas, hw)
                else:
                    lease.canvas = np.asarray(canvas)
            elif b.slab is not None and hasattr(b.slab, "write_hw"):
                b.slab.write_hw(lease.index, hw)
        with self._cond:
            if lease.state == _PENDING:
                lease.state = _READY
                lease.hw = (int(hw[0]), int(hw[1]))
                lease.committed_at = time.monotonic()
                b.n_pending -= 1
                b.n_ready += 1
                self._settle_locked(lease)
                if lease.slab_held:
                    b.slab.drop_lease()  # writing is done
                    lease.slab_held = False
                self._cond.notify_all()
            elif lease.slab_held:
                # Force-expired while we were decoding: the batch already
                # left without this row; just stop holding the slab back.
                b.slab.drop_lease()
                lease.slab_held = False
        return lease.future

    def _release_lease(self, lease: SlotLease):
        b = lease.builder
        with self._cond:
            if lease.slab_held:
                b.slab.drop_lease()
                lease.slab_held = False
            if lease.state == _PENDING:
                lease.state = _HOLE
                b.n_pending -= 1
                b.n_holes += 1
                self._dec_pending_locked(b)
                self._holes_total += 1
                self._settle_locked(lease)
                try:
                    lease.future.set_exception(
                        RuntimeError("slot lease released"))
                except Exception:
                    pass  # nobody should await a released slot anyway
                self._cond.notify_all()
            elif lease.state == _READY and not b.dispatched:
                # Abandoning a committed slot (e.g. a sibling upload 400d):
                # the row becomes a hole instead of wasting device work.
                lease.state = _HOLE
                b.n_ready -= 1
                b.n_holes += 1
                self._dec_pending_locked(b)
                self._holes_total += 1
                self._cond.notify_all()
            # READY + dispatched: too late — the result is simply dropped.

    def _settle_locked(self, lease: SlotLease):
        """``lease`` left PENDING (committed, released or force-expired):
        on an arena that ships by pages (engine.RaggedSlab.settle), hand the
        pages this completes to the engine's shipper, which copies them to
        the device while the batch is still open. Queues work, never waits."""
        b = lease.builder
        if not getattr(b.slab, "paged", False):
            return
        pages = b.slab.settle(lease.index)
        if pages:
            if b.seq is None:
                self._batch_seq += 1
                b.seq = self._batch_seq
            self.engine.ship_pages(b.slab, pages, b.seq)

    def flush_bulk(self) -> None:
        """Seal every open bulk builder NOW. The job runner calls this
        after staging a chunk: a full chunk already sealed at capacity (a
        no-op here), the manifest's partial tail must not wait out the
        wide bulk window — and a padded-bucket execute costs the same as
        a full one, so the runner (which KNOWS the chunk is complete) is
        the right place to decide, not a timer guessing."""
        with self._cond:
            for b in [b for b in self._open.values() if b.bulk]:
                self._close_builder_locked(b, "flush")
            self._cond.notify_all()

    # -------------------------------------------------------------- sealing

    def _update_delay(self) -> float:
        """One controller step: move the live window toward a target set by
        outstanding-slot depth (none → 0, ≥max_batch backlog → the cap)."""
        if not self.adaptive_delay:
            return self.max_delay_s
        depth = self._pending_slots
        target = self.max_delay_s * min(1.0, depth / max(1, self.max_batch - 1))
        self._delay_s += 0.25 * (target - self._delay_s)
        # Clamp: float drift must never push the window outside its bounds.
        self._delay_s = min(self.max_delay_s, max(0.0, self._delay_s))
        return self._delay_s

    def _expire_locked(self, b: _Builder, now: float, timeout: float):
        expired = False
        for lease in b.leases:
            if lease.state == _PENDING and now - lease.leased_at > timeout:
                lease.state = _HOLE
                b.n_pending -= 1
                b.n_holes += 1
                self._dec_pending_locked(b)
                self._lease_timeouts_total += 1
                self._holes_total += 1
                self._settle_locked(lease)
                expired = True
                try:
                    lease.future.set_exception(LeaseExpired(
                        f"slot lease expired after {timeout:.1f}s"))
                except Exception:
                    pass
                # The slab refcount is deliberately NOT dropped here: the
                # lessee thread may still be decoding into the row. The row
                # is padded, its future failed, and the slab returns to the
                # pool only once that thread resolves the lease.
        if expired:
            # Freed cap slots must wake lease() waiters NOW, not at their
            # next 250 ms poll (the other two decrement sites notify too).
            self._cond.notify_all()

    def _shed_dead_rows_locked(self, b: _Builder, now: float):
        """Turn committed rows whose deadline already passed into holes
        before the batch takes a pipeline slot (the seal-time half of
        deadline-aware shedding: admission predicts, the sealer
        enforces). The future fails with DeadlineExceeded — the awaiting
        worker answers 504 immediately instead of after device time is
        spent on a result nobody will read."""
        shed = False
        for lease in b.leases:
            if (lease.state == _READY and lease.deadline is not None
                    and now > lease.deadline):
                lease.state = _HOLE
                b.n_ready -= 1
                b.n_holes += 1
                self._dec_pending_locked(b)
                self._holes_total += 1
                self._deadline_seal_sheds_total += 1
                shed = True
                try:
                    lease.future.set_exception(DeadlineExceeded(
                        "deadline passed while the request waited for "
                        "dispatch",
                        retry_after_s=self._retry_after_locked(),
                    ))
                except Exception:
                    pass  # caller already timed out and moved on
        if shed:
            # Freed cap slots must wake lease() waiters NOW (same
            # contract as _expire_locked's notify).
            self._cond.notify_all()

    def _pick_replica_locked(self, mkey, bound: int | None = None) -> int | None:
        """Routing decision for one sealed interactive batch of ``mkey`` =
        (canvas-bucket key, bulk flag): among replicas with pipeline-depth
        headroom for this bucket, the least-loaded by the engine's
        in-flight dispatch count, round-robin cursor order breaking ties —
        so balanced load walks the chips cyclically and an unbalanced one
        self-corrects. An arena ``bound`` to a replica by its early pages
        goes there alone. None = every replica is at depth."""
        n = self._n_replicas
        if self._calls_full_locked():
            return None
        if bound is not None:
            return (bound if self._inflight_by_key.get((mkey, bound), 0)
                    < self.pipeline_depth else None)
        if n == 1:
            return (0 if self._inflight_by_key.get((mkey, 0), 0)
                    < self.pipeline_depth else None)
        cands = [r for r in range(n)
                 if self._inflight_by_key.get((mkey, r), 0) < self.pipeline_depth]
        if not cands:
            return None
        loads = self.engine.replica_loads()
        start = self._rr
        return min(cands, key=lambda r: (loads[r], (r - start) % n))

    def _pick_bulk_replica_locked(self, bound: int | None = None) -> int:
        """Bulk batches are depth-gated globally (the gate below), not per
        (bucket, replica) — routing just spreads them least-loaded so a
        job fills whichever chip group interactive traffic uses least
        (an arena ``bound`` by its early pages goes to its replica)."""
        n = self._n_replicas
        if bound is not None:
            return bound
        if n == 1:
            return 0
        loads = self.engine.replica_loads()
        start = self._rr
        return min(range(n), key=lambda r: (loads[r], (r - start) % n))

    def _bulk_gate_open_locked(self, now: float, consume: bool = True,
                               tenant: str | None = None,
                               rows: int = 0) -> bool:
        """Strict-priority admission for the bulk traffic class: a sealed
        bulk batch may take device time only when no interactive batch is
        waiting to dispatch, the interactive pipeline is IDLE (zero
        interactive batches in flight — an interactive batch that sealed
        during a bulk execute always runs before the next bulk batch, so
        alternation under mixed load is interactive-first), and bulk's
        own in-flight cap has room. Every fetch completion notifies the
        condition, so a closed gate re-evaluates the moment interactive
        pressure drops — no polling, no lost wakeup.

        Anti-starvation valve: closed-loop interactive clients keep the
        pipeline non-idle FOREVER, and strict priority must degrade bulk
        to slow, not to zero — a bulk batch gated continuously for
        ``bulk_starvation_s`` is admitted once and the clock re-arms, so
        a saturated server still drains one bulk batch per window (the
        amortized tail cost is one execute quantum per window).

        ``consume=False`` is the builder-CLOSE decision's peek: it answers
        "would this batch be admitted?" without firing the valve, so the
        single admission the valve grants is spent by the DISPATCH
        decision in the same sealer pass — not consumed closing the
        builder and then re-gated for a second full window.

        Precedence rule (ISSUE 13 satellite): the TENANT QUOTA check
        runs before every admission below — including the
        anti-starvation valve — so a quota-exhausted tenant's job can
        never ride the valve past its budget. A quota hold does not
        start (or consume) the starvation clock either: quota pressure
        is the tenant's own doing, not interactive preemption, and the
        valve exists to bound the latter only."""
        if self._bulk_inflight >= self.bulk_inflight_cap:
            return False  # own cap, not interactive pressure: no clock
        if (self.admission is not None
                and not self.admission.peek(tenant, max(1, rows))):
            if consume:
                self._bulk_quota_holds += 1
            return False  # tenant budget, not interactive pressure: no clock
        if (any(not c.bulk for c in self._closing)
                or self._inflight_total - self._bulk_inflight > 0):
            if self._bulk_gated_since is None:
                self._bulk_gated_since = now
            elif now - self._bulk_gated_since >= self.bulk_starvation_s:
                if consume:
                    self._bulk_starvation_total += 1
                    self._bulk_gated_since = None  # one through; re-arm
                return True
            return False
        self._bulk_gated_since = None
        return True

    def _call_slots_locked(self) -> int | None:
        """Calls the engine's ceiling on calls in flight over all buckets
        (``InferenceEngine.max_calls_in_flight``: what the device's memory
        holds beside the weights, by the compiled programs' temporaries)
        leaves free; None on an engine that knows no ceiling."""
        cap = getattr(self.engine, "max_calls_in_flight", None)
        return cap - self._inflight_total if cap else None

    def _calls_full_locked(self) -> bool:
        slots = self._call_slots_locked()
        return slots is not None and slots <= 0

    def _promised_before_locked(self, b: _Builder) -> int:
        """Call slots promised ahead of ``b``: interactive batches sealed
        and waiting that opened before it (they dispatch oldest first)."""
        return sum(1 for c in self._closing
                   if not c.bulk and c.opened_at <= b.opened_at)

    def _depth_free_locked(self, mkey) -> bool:
        # Headroom check only — no engine.route_lock hop, no least-loaded
        # scan. It runs per open builder on every sealer wakeup; the real
        # replica pick happens once, at the dispatch decision.
        return any(
            self._inflight_by_key.get((mkey, r), 0) < self.pipeline_depth
            for r in range(self._n_replicas)
        )

    def _pick_action_locked(self, now: float):
        """Seal/dispatch decision for one sealer wakeup. Returns
        ("dispatch"|"discard", builder) or None to keep waiting. A
        "dispatch" return has already taken its pipeline-depth slot."""
        draining = not self._running
        grace = min(self.lease_timeout_s, 2.0) if draining else self.lease_timeout_s
        for b in list(self._open.values()):
            self._expire_locked(b, now, grace)
        # Free call slots under the engine's ceiling (None: no ceiling).
        # With one, builders are visited oldest first, so that the oldest
        # past their windows take the slots.
        slots = self._call_slots_locked()
        by_age = slots is not None
        opened = list(self._open.values())
        if by_age:
            opened.sort(key=lambda x: x.opened_at)
        held = False
        for b in opened:
            # Past-deadline builders close only when every in-flight decode
            # resolved AND a dispatch slot is free for them: their bucket's
            # pipeline depth, and under the engine's ceiling a call slot
            # that no sealed batch opened before them is promised. Closing
            # earlier would freeze the batch's size while it sits
            # undispatchable, and fragment its canvas's new arrivals into
            # fresh builders — so while the device is the bottleneck a
            # builder keeps accepting and GROWS toward capacity. A bulk
            # builder closes against its own gate instead: while
            # interactive load holds the device, the bulk batch keeps
            # accepting and GROWS toward bulk_max_batch. The pending-decode
            # wait is bounded — leases expire above.
            if draining:
                self._close_builder_locked(b, "drain")
            elif len(b.leases) >= b.capacity:
                self._close_builder_locked(b, "full")
            elif now < b.deadline or b.n_pending:
                continue
            elif b.bulk:
                if self._bulk_gate_open_locked(now, consume=False,
                                               tenant=b.tenant,
                                               rows=b.n_ready):
                    self._close_builder_locked(b, "window")
            elif self._depth_free_locked((b.key, False)):
                if slots is None or self._promised_before_locked(b) < slots:
                    self._close_builder_locked(b, "window")
                else:
                    held = True
        if held:
            self._life["window_holds_total"] += 1
        for b in self._closing:
            self._expire_locked(b, now, grace)
        # Interactive builders first, always: the bulk class is strictly
        # lower priority and must never jump a sealed interactive batch.
        # Under a ceiling, oldest first among each: a builder sealed into
        # a slot ahead of younger sealed batches takes that slot.
        order = ((lambda x: (x.bulk, x.opened_at)) if by_age
                 else (lambda x: x.bulk))
        for b in sorted(self._closing, key=order):
            if b.n_pending:
                continue  # a lessee is still decoding; bounded by expiry
            if not b.bulk:
                # Seal-time deadline re-check: a row whose deadline passed
                # while it waited (interactive pressure, a slow replica)
                # becomes a hole NOW — its client already gave up, and
                # shipping it would spend device time on a dead request.
                self._shed_dead_rows_locked(b, now)
            if b.n_ready == 0:
                self._closing.remove(b)
                b.dispatched = True
                if b.bulk and not any(c.bulk for c in self._closing):
                    # The last gated bulk batch evaporated into holes (a
                    # cancel's abort released every lease): stop the
                    # starvation clock, or a job arriving much later
                    # inherits an instantly-open valve and injects a bulk
                    # quantum into the interactive tail with zero actual
                    # gated time.
                    self._bulk_gated_since = None
                return ("discard", b)
            # The replica an arena's early pages went to (None: unbound).
            bound = getattr(b.slab, "replica", None)
            if b.bulk:
                if not draining and not self._bulk_gate_open_locked(
                        now, tenant=b.tenant, rows=b.n_ready):
                    # Gated: interactive owns the device right now. Hold
                    # the builder (fetch completions re-open the gate,
                    # the starvation valve bounds the wait); during
                    # drain the gate lifts so stop() can flush.
                    self._bulk_gate_holds += 1
                    continue
                replica = self._pick_bulk_replica_locked(bound)
            else:
                # Per-bucket pipeline gate: while this bucket already has
                # pipeline_depth batches dispatched-and-unfetched, hold the
                # builder and BLOCK on the condition (the completion pool
                # notifies when a fetch lands); meanwhile new leases keep
                # filling open builders, so batches grow exactly when the
                # device is the bottleneck. The launch handoff itself never
                # blocks — transfer of batch N+1 starts the moment its
                # builder seals, it does NOT wait for batch N's fetch.
                replica = self._pick_replica_locked((b.key, False), bound)
                if draining and replica is None:
                    # Drain must make progress even with every replica at
                    # depth: overshoot the gate round-robin rather than
                    # strand the builder (completions are still fetching).
                    replica = bound if bound is not None else self._rr % self._n_replicas
            if replica is not None:
                self._closing.remove(b)
                b.dispatched = True
                b.replica = replica
                self._rr = (replica + 1) % self._n_replicas
                mkey = (b.key, b.bulk)
                self._inflight_by_key[(mkey, replica)] = (
                    self._inflight_by_key.get((mkey, replica), 0) + 1
                )
                self._inflight_total += 1
                self._inflight_peak = max(self._inflight_peak,
                                          self._inflight_total)
                if b.bulk:
                    self._bulk_inflight += 1
                    if self.admission is not None:
                        # Charge the job's tenant for the device time the
                        # batch is about to take (the gate only PEEKED;
                        # oversized batches take token debt — see
                        # AdmissionController.charge).
                        self.admission.charge(b.tenant, b.n_ready)
                return ("dispatch", b)
        return None

    def _next_wake_locked(self, now: float) -> float | None:
        wake = None
        for b in self._open.values():
            # A past-deadline builder still open has pending decodes or no
            # dispatch slot (else _pick_action_locked closed it); its next
            # event is a commit or a batch done (both notify the condition)
            # or a lease expiry (covered below) — re-waking on the stale
            # deadline would just spin.
            if b.deadline > now:
                wake = b.deadline if wake is None else min(wake, b.deadline)
        # MUST mirror _pick_action_locked's expiry horizon: during drain
        # leases expire after the (shorter) drain grace, and sleeping to the
        # full lease timeout instead would overshoot stop()'s sealer join —
        # stranding committed siblings with the launch pool already gone.
        grace = (self.lease_timeout_s if self._running
                 else min(self.lease_timeout_s, 2.0))
        for blist in (self._open.values(), self._closing):
            for b in blist:
                if not b.n_pending:
                    continue
                for lease in b.leases:
                    if lease.state == _PENDING:
                        t = lease.leased_at + grace
                        wake = t if wake is None else min(wake, t)
        # A gated-ready bulk batch must wake at its starvation deadline
        # even if no fetch completion happens to notify first (interactive
        # load normally notifies constantly; this covers the quiet case).
        # Past-deadline OPEN bulk builders count too: their close decision
        # peeks the same gate, so the valve deadline is their next event.
        if self._bulk_gated_since is not None and (
                any(b.bulk for b in self._closing)
                or any(b.bulk and b.deadline <= now
                       for b in self._open.values())):
            t = self._bulk_gated_since + self.bulk_starvation_s
            wake = t if wake is None else min(wake, t)
        if wake is None:
            return None  # nothing assembling: sleep until notified
        return max(0.0005, wake - now)

    def _seal_wait_label_locked(self) -> str:
        """The canvas of the builder the sealer waits on (``c4096``: the
        first that closed, else the first that opened), for its
        annotation; empty with none. What is open, closing and in flight is
        in ``/stats``."""
        b = self._closing[0] if self._closing else next(
            iter(self._open.values()), None)
        return f"c{canvas_side(b.key)}" if b is not None else ""

    def _seal_loop(self):
        while True:
            with self._cond:
                while True:
                    now = time.monotonic()
                    action = self._pick_action_locked(now)
                    if action is not None:
                        break
                    if not self._running and not self._open and not self._closing:
                        return  # drained: every builder dispatched/discarded
                    with stage(None, "seal_wait", self._seal_wait_label_locked()):
                        self._cond.wait(timeout=self._next_wake_locked(now))
            kind, b = action
            if kind == "dispatch":
                self._hand_off(b)
            else:
                self._recycle(b)
                # Discarded builders count as sealed too (the /metrics help
                # text promises "dispatched or discarded") and their exit
                # must wake lease()/seal waiters like a dispatch would.
                self._finish_seal(b, 0)

    def _recycle(self, b: _Builder):
        """Return a builder's slab to the engine pool: discarded (all-hole)
        builders AND batches whose dispatch failed or was abandoned at
        shutdown. Routed through the slab's lease refcount, so a slab
        whose buffers were already handed to the device only becomes
        pool-eligible once every straggling lessee resolves — and its
        dropped outputs are never fetched, so any aliased device read is
        harmless."""
        if b.slab is not None and hasattr(self.engine, "release_staging"):
            self.engine.release_staging(b.slab)
        b.leases = []  # as in _launch: no builder↔lease cycle around the slab

    def _hand_off(self, b: _Builder):
        """Seal one builder and enqueue it for the launch pool. The sealer
        does NO device work: the outstanding-slot cap frees here (decode of
        the next batch proceeds while this one transfers), and the
        host→device transfer runs on a launch thread."""
        ready = [l for l in b.leases if l.state == _READY]
        # The batch record: identity, then the lifecycle's stamps in order
        # (all time.monotonic(); None until reached). The engine fills
        # t_put (both device_puts returned: the copy is *enqueued*), t_pre
        # (unpack enqueued), the flight's t_h2d_done, t_dev_start, t_ready
        # and late (engine.Flight), h2d_bytes, d2h_bytes and unpack_kernel
        # (the ragged unpack ran the Mosaic kernel) where it is handed the
        # record; trace_ids are the request spans that rode.
        rec = {
            "seq": 0, "key": b.key, "rows": len(ready), "bucket": None,
            "replica": b.replica, "bulk": b.bulk, "reason": b.reason,
            "trace_ids": sorted({l.span.trace_id for l in ready
                                 if l.span is not None}),
            "t_open": b.opened_at, "t_seal": time.monotonic(),
            "t_launch": None, "t_put": None, "t_pre": None,
            "t_launched": None, "t_h2d_done": None, "t_dev_start": None,
            "t_ready": None, "t_fetch": None, "t_done": None, "late": (),
            "h2d_bytes": None, "d2h_bytes": None, "unpack_kernel": False,
            "h2d_pages": None, "h2d_pages_early": None, "h2d_early_bytes": None,
        }
        with self._cond:
            self._dec_pending_locked(b, len(ready))
            self._sealed_total += 1
            if b.bulk:
                self._bulk_sealed_total += 1
                self._bulk_images_total += len(ready)
            if b.seq is None:
                self._batch_seq += 1
                b.seq = self._batch_seq
            rec["seq"] = b.seq
            self._timeline.append(rec)
            life = self._life
            life["batches_total"] += 1
            life["by_reason"][b.reason] += 1
            life["open_s_total"] += rec["t_seal"] - rec["t_open"]
            self._cond.notify_all()  # lease() waiters + next seal decision
        self._launch_q.put((b, ready, rec))

    def _finish_seal(self, b: _Builder, n_ready: int):
        with self._cond:
            self._dec_pending_locked(b, n_ready)
            self._sealed_total += 1
            self._cond.notify_all()  # lease() waiters + next seal decision

    def _batch_done(self, rec: dict, ok: bool = True):
        """One in-flight batch left the pipeline (fetched, or failed when
        not ``ok``; its ``t_done`` stamped): free its ((bucket, bulk),
        replica) depth slot, close its lifecycle counters and wake the
        sealer — the wakeup that also re-evaluates the bulk gate."""
        mkey = (rec["key"], rec["bulk"])
        with self._cond:
            life, t_done = self._life, rec["t_done"]
            life["inflight_s_total"] += t_done - rec["t_launched"]
            self._launched.pop(rec["seq"], None)
            if ok and rec["t_ready"] is not None:
                t_h2d, t_dev, t_ready = (rec["t_h2d_done"], rec["t_dev_start"],
                                         rec["t_ready"])
                life["h2d_s_total"] += t_h2d - rec["t_launch"]
                life["device_queue_s_total"] += t_dev - t_h2d
                life["device_s_total"] += t_ready - t_dev
                life["d2h_s_total"] += t_done - t_ready
                life["stamps_late_total"] += len(rec["late"])
                self._phases += [(rec["t_launch"], t_h2d, False),
                                 (t_dev, t_ready, True)]
            self._sweep_h2d_bound_locked()
            life["h2d_bytes_total"] += rec["h2d_bytes"] or 0
            life["d2h_bytes_total"] += rec["d2h_bytes"] or 0
            life["h2d_early_bytes_total"] += rec["h2d_early_bytes"] or 0
            life["h2d_pages_early_total"] += rec["h2d_pages_early"] or 0
            life["h2d_pages_total"] += rec["h2d_pages"] or 0
            life["unpack_kernel_batches_total"] += bool(rec["unpack_kernel"])
            # What the model itself counted in this call (a token decoder:
            # tokens, token slots, the router's picks, decode steps).
            for name, value in (rec.get("model_counters") or {}).items():
                life[f"{name}_total"] = life.get(f"{name}_total", 0.0) + value
            self._launched_now -= 1
            if self._launched_now == 0:
                self._starved_since = t_done
            slot = (mkey, rec["replica"])
            n = self._inflight_by_key.get(slot, 0) - 1
            if n > 0:
                self._inflight_by_key[slot] = n
            else:
                self._inflight_by_key.pop(slot, None)
            self._inflight_total -= 1
            if mkey[1]:
                self._bulk_inflight -= 1
            self._cond.notify_all()

    def _sweep_h2d_bound_locked(self):
        """Bring the h2d-bound clock up to the earliest ``t_launch`` of a
        batch still in flight (or to now, with none): every copy or device
        phase that can lie before that is a done batch's, and known."""
        horizon = min(self._launched.values(), default=time.monotonic())
        lo = self._phases_swept
        if horizon <= lo:
            return
        # +1/-1 at each end of a phase clipped to [lo, horizon]; the clock
        # runs where some copy and no device phase is open.
        edges = []
        for a, b, dev in self._phases:
            a, b = max(a, lo), min(b, horizon)
            if a < b:
                edges += [(a, 1, dev), (b, -1, dev)]
        edges.sort()
        open_ = [0, 0]  # copies, device phases
        t_prev, bound = lo, 0.0
        for t, step, dev in edges:
            if open_[0] and not open_[1]:
                bound += t - t_prev
            open_[dev] += step
            t_prev = t
        self._life["h2d_bound_s_total"] += bound
        self._phases = [p for p in self._phases if p[1] > horizon]
        self._phases_swept = horizon

    # ------------------------------------------------------------ launching

    def _launch_loop(self):
        while True:
            item = self._launch_q.get()
            if item is None:
                return
            self._launch(*item)

    def _launch(self, b: _Builder, ready: list[SlotLease], rec: dict):
        """Ship one sealed builder to the device (launch-pool thread): pad
        holes, one device_put, execute enqueue, async D2H start. Transfers
        of consecutive batches overlap because the pool has more than one
        thread and the sealer never waits for a launch to finish."""
        with self._cond:
            # Read under the lock, so that the starved clock's 0→1 and 1→0
            # transitions are ordered as their stamps are.
            t0 = rec["t_launch"] = time.monotonic()
            life = self._life
            life["launch_wait_s_total"] += t0 - rec["t_seal"]
            if self._launched_now == 0:
                life["starved_s_total"] += max(0.0, t0 - self._starved_since)
            self._launched_now += 1
            self._launched[rec["seq"]] = t0
        for l in ready:
            if l.span is not None:
                # add_max: a multi-image request's legs ride concurrent
                # batches; the stage merges as the slowest leg so the span's
                # stage sum still tiles the request's wall time.
                l.span.add_max("queue_wait", t0 - l.committed_at)
                l.span.note_append("batches", rec["seq"])
        spans = [l.span for l in ready if l.span is not None]
        traced = self._engine_takes_rec
        try:
            if self.chaos is not None and self.chaos.dispatch_fault():
                # Inside the try: an injected dispatch error exercises
                # EXACTLY the organic cleanup path below (fail futures,
                # recycle slab, free the depth slot) — the chaos tests
                # assert that path leaks nothing.
                raise ChaosError("chaos: injected dispatch failure")
            if b.slab is not None:
                n = max(l.index for l in ready) + 1
                if hasattr(b.slab, "write_hw"):
                    for l in b.leases:
                        if l.state == _HOLE and l.index < n:
                            b.slab.write_hw(l.index, (1, 1))  # pad the hole
                bucket = (self.engine.pick_batch_bucket(n)
                          if hasattr(self.engine, "pick_batch_bucket")
                          else b.slab.bucket)
                # Routed engines get the sealer's replica decision; fakes
                # and embedders with the plain signatures never see the
                # keyword.
                kw = {"replica": b.replica} if self._route else {}
                if traced:
                    kw["rec"] = rec
                if getattr(b.slab, "is_ragged", False):
                    # Ragged wire: ship the tight arena prefix + meta; the
                    # engine's jitted unpack stage rebuilds the canvases on
                    # device.
                    handle = self.engine.dispatch_ragged(b.slab, n,
                                                         spans=spans, **kw)
                elif traced:
                    # The engine stamps the batch's flight into rec, which
                    # the completion thread turns into the spans' device
                    # stages; spans= (the replica note) keeps staging-API
                    # fakes and embedders with the plain signature working.
                    handle = self.engine.dispatch_staged(b.slab, n,
                                                         spans=spans, **kw)
                else:
                    handle = self.engine.dispatch_staged(b.slab, n, **kw)
                    t_disp = time.monotonic()
                    for s in spans:
                        s.add_max("device_dispatch", t_disp - t0)
                idxs = [l.index for l in ready]
            else:
                t_stage = time.monotonic()
                canvases = np.stack([l.canvas for l in ready])
                hws = np.array([l.hw for l in ready], np.int32)
                for s in spans:
                    s.add_max("staging_write", time.monotonic() - t_stage)
                bucket = len(ready)
                kw = {"replica": b.replica} if self._route else {}
                handle = self.engine.dispatch_batch(canvases, hws, **kw)
                t_disp = time.monotonic()
                for s in spans:
                    s.add_max("device_dispatch", t_disp - t0)
                idxs = list(range(len(ready)))
        except Exception as e:  # batch fails → its requests fail, server lives
            log.exception("dispatch of batch of %d failed", len(ready))
            self._fail(ready, e)
            rec["t_launched"] = rec["t_done"] = time.monotonic()
            # The batch will never be fetched, so the slab must go back to
            # the pool here (routed through its lease refcount) — otherwise
            # every transient dispatch failure strands one slab's host
            # memory. Any aliased device read of dropped outputs is
            # harmless: nobody fetches them.
            self._recycle(b)
            self._batch_done(rec, ok=False)
            return
        rec["t_launched"] = time.monotonic()
        rec["bucket"] = bucket
        # The builder is done with its leases (``ready`` carries them on).
        # Dropping the list breaks the builder↔lease reference cycle, which
        # would otherwise keep the slab (up to 1.6 GB at canvas 4096 × batch
        # 32, dropped by the pool's byte budget after every batch) alive
        # until the next full garbage collection: tens of gigabytes of host
        # memory in a 30 s window (PERF.md section 6, PR 27).
        b.leases = []
        for l in ready:
            if l.span is not None:
                # The compiled bucket this request's batch ran at — the
                # access log's join key for padding-waste analysis.
                l.span.note("batch_bucket", bucket)
        self.stats.record_batch(len(ready), bucket)
        self._record_padding(b.key, bucket, ready, slab=b.slab)
        self._done_q.put((ready, idxs, handle, rec))

    def _record_padding(self, key, bucket: int, ready: list[SlotLease],
                        slab=None):
        """Fold one dispatched batch into the per-(canvas, batch-bucket)
        padding-waste counters: how many dispatched rows carried requests,
        and how many of the shipped canvas pixels were real image. On the
        ragged wire the shipped pixels are the quantized arena prefix
        (rows_shipped × canvas²) — the tight wire is exactly what the
        padded_px_fraction gauge must credit; the rows axis stays at the
        compiled bucket, because the model still executes bucket rows."""
        s = canvas_side(key)
        px_real = sum(l.hw[0] * l.hw[1] for l in ready if l.hw)
        if slab is not None and getattr(slab, "is_ragged", False):
            px_dispatched = slab.rows_shipped(bucket) * s * s
        else:
            px_dispatched = bucket * s * s
        with self._cond:
            cell = self._padding.get((s, bucket))
            if cell is None:
                cell = self._padding[(s, bucket)] = [0, 0, 0, 0, 0]
            cell[0] += 1
            cell[1] += len(ready)
            cell[2] += bucket
            cell[3] += px_real
            cell[4] += px_dispatched

    # ----------------------------------------------------------- completion

    def _fetch_loop(self):
        while True:
            item = self._done_q.get()
            if item is None:
                return
            ready, idxs, handle, rec = item
            if self.chaos is not None:
                # Straggling-chip injection: sleep on the completion
                # thread (no lock held), so the batch occupies its
                # pipeline-depth slot longer — building real
                # backpressure for the deadline/ladder machinery.
                delay = self.chaos.fetch_delay()
                if delay > 0:
                    time.sleep(delay)
            rec["t_fetch"] = time.monotonic()
            try:
                if self._engine_takes_rec:
                    outs = self.engine.fetch_outputs(handle, rec=rec)
                else:
                    outs = self.engine.fetch_outputs(handle)
            except Exception as e:
                log.exception("fetch of batch of %d failed", len(ready))
                self._fail(ready, e)
                rec["t_done"] = time.monotonic()
                self._batch_done(rec, ok=False)
                continue
            now = time.monotonic()
            rec["t_done"] = now
            t_launch, t_ready = rec["t_launch"], rec["t_ready"]
            if t_ready is not None:
                # The flight's own stamps: the copy, the wait behind
                # earlier calls plus the device's work, the copy back.
                t_h2d = rec["t_h2d_done"]
                stages = (("device_transfer", t_h2d - t_launch),
                          ("device_execute", t_ready - t_h2d),
                          ("device_d2h", now - t_ready))
            else:
                stages = (("device_execute", now - rec["t_launched"]),)
            for l, oi in zip(ready, idxs):
                row = tuple(o[oi] for o in outs)
                if l.span is not None:
                    # Stamp BEFORE resolving the future: once set_result
                    # runs, the HTTP worker owns the span again.
                    for name, dur in stages:
                        l.span.add_max(name, dur)
                try:
                    l.future.set_result(row)
                except Exception:
                    pass  # caller timed out and cancelled — result dropped
                self.stats.record(
                    latency_s=now - l.committed_at,
                    queue_s=t_launch - l.committed_at,
                    device_s=now - t_launch,
                    batch_size=len(ready),
                )
            self._batch_done(rec)

    def _fail(self, leases: list[SlotLease], e: Exception):
        now = time.monotonic()
        for l in leases:
            try:
                l.future.set_exception(e)
            except Exception:
                pass  # already cancelled/resolved
            # Errored requests keep their timing: failures are often the
            # slowest requests (timeouts, poisoned batches) and must stay
            # visible in the error-latency window, not vanish.
            self.stats.record_error(
                latency_s=now - (l.committed_at or l.leased_at))

    # ---------------------------------------------------------------- stats

    @property
    def queue_depth(self) -> int:
        """Leased-but-undispatched slots — the assembly backlog."""
        return self._pending_slots

    @property
    def inflight_batches(self) -> int:
        """Batches sealed-and-launched but not yet fetched (all buckets)."""
        return self._inflight_total

    @property
    def current_delay_ms(self) -> float:
        """Live adaptive assembly window (ms) — the value /stats reports."""
        return self._delay_s * 1e3

    def builder_stats(self) -> dict:
        """Builder occupancy + lease/pipeline telemetry for /stats and
        /metrics."""
        with self._cond:
            by_replica = {}
            for (_key, r), cnt in self._inflight_by_key.items():
                by_replica[r] = by_replica.get(r, 0) + cnt
            return {
                "model": self.name,
                "ragged": self.ragged,
                "open_builders": len(self._open) + len(self._closing),
                "leased_slots": self._pending_slots,
                "batches_sealed_total": self._sealed_total,
                "lease_timeouts_total": self._lease_timeouts_total,
                "holes_total": self._holes_total,
                "pipeline_depth": self.pipeline_depth,
                "inflight_batches": self._inflight_total,
                "inflight_peak": self._inflight_peak,
                "replicas": self._n_replicas,
                # Batches in flight per dispatch replica (all buckets) —
                # the batcher-side view of placement routing; the engine's
                # staging_stats carries the device-side twin.
                "inflight_by_replica": {
                    str(r): by_replica.get(r, 0)
                    for r in range(self._n_replicas)
                } if self._n_replicas > 1 else {},
                "max_queue": self.max_queue,
                "backlog_rejections_total": self._rejects_total,
                # Overload sheds (ISSUE 13): deadline sheds split by
                # where they fired (lease-time admission vs the sealer's
                # dead-row re-check) + interactive quota sheds. The
                # chaos suite sums these with errors against offered
                # load.
                "deadline_sheds_total": self._deadline_sheds_total,
                "deadline_seal_sheds_total": self._deadline_seal_sheds_total,
                "quota_sheds_total": self._quota_sheds_total,
                # Padding waste per (canvas, batch-bucket): dispatched-row
                # vs real-row counts and shipped-canvas vs real-image
                # pixels — the measured fractions ROADMAP item 5 starts
                # from, and the batcher-side half of /stats "economics".
                "padding": {
                    f"{s}x{bk}": {
                        "canvas": s,
                        "batch_bucket": bk,
                        "batches": c[0],
                        "rows_real": c[1],
                        "rows_dispatched": c[2],
                        "padded_rows_fraction": round(
                            1.0 - c[1] / c[2], 4) if c[2] else 0.0,
                        "px_real": c[3],
                        "px_dispatched": c[4],
                        "padded_px_fraction": round(
                            1.0 - c[3] / c[4], 4) if c[4] else 0.0,
                    }
                    for (s, bk), c in sorted(self._padding.items())
                },
                # Bulk traffic class (jobs): its own staging/pipeline view,
                # next to the interactive numbers it is forbidden to touch.
                "bulk": {
                    "max_batch": self.bulk_max_batch,
                    "inflight_cap": self.bulk_inflight_cap,
                    "leased_slots": self._bulk_pending,
                    "inflight_batches": self._bulk_inflight,
                    "batches_sealed_total": self._bulk_sealed_total,
                    "images_sealed_total": self._bulk_images_total,
                    "gate_holds_total": self._bulk_gate_holds,
                    # Batches admitted by the anti-starvation valve
                    # (sustained interactive load never went idle).
                    "starvation_dispatches_total": self._bulk_starvation_total,
                    # Gate closed on the job tenant's token budget —
                    # quota precedes the valve (ISSUE 13 satellite), so
                    # these holds never accrue starvation credit.
                    "quota_holds_total": self._bulk_quota_holds,
                },
            }

    def lifecycle_stats(self) -> dict:
        """The ``/stats → batcher.lifecycle`` block: the cumulative
        counters above plus ``now_s``, the clock they were read at, and the
        starved clock brought up to it (the h2d-bound clock as far as the
        earliest batch in flight), so that a share is a delta over a delta
        of two reads."""
        with self._cond:
            self._sweep_h2d_bound_locked()
            now = time.monotonic()
            out = {**self._life, "by_reason": dict(self._life["by_reason"])}
            if self._launched_now == 0:
                out["starved_s_total"] += max(0.0, now - self._starved_since)
            out["now_s"] = now
            return out

    def batch_timeline(self) -> list[dict]:
        """Recent per-batch lifecycle records (monotonic stamps): builder
        ``t_open`` → ``t_seal`` (assembly/decode window; ``reason`` says
        why it sealed) → ``t_launch`` → ``t_put`` (both ``device_put``s
        returned) → ``t_pre`` (unpack enqueued) → ``t_launched`` (execute
        enqueued, D2H started) → ``t_done`` (outputs on host); the flight's
        ``t_h2d_done`` (copy landed), ``t_dev_start`` and ``t_ready``
        (outputs computed) with ``late``; ``t_fetch`` (a completion thread
        turned to it); ``h2d_bytes``/``d2h_bytes`` and the ``trace_ids``
        that rode. In-flight batches carry None for
        stages not reached yet. The raw material for overlap analysis —
        bench.py's ``pipeline`` block computes busy-time(decode ∥ execute)
        from exactly this."""
        with self._cond:
            return [dict(r) for r in self._timeline]
