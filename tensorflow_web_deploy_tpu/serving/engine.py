"""Inference engine: converted graph → sharded, precompiled serving function.

Replaces the reference's L2 runtime (``load_graph()`` + ``sess.run`` on one
GPU; SURVEY.md §3.1–3.3) with the TPU pipeline:

    frozen .pb ──convert──▶ fn(params, x) ──compose──▶ serve_fn(params, canvases, hws)
                                              │   on-device resize+normalize (ops.image)
                                              │   model forward (bfloat16 on the MXU)
                                              │   postprocess (top-k probs / NMS)
                                              ▼
            jax.jit(in_shardings=(replicated params, batch over 'data'))
            precompiled per (canvas bucket, batch bucket) + warmed up

Compilation happens once at startup (the reference defers to first
``sess.run``; we warm every shape so no request pays a compile stall —
SURVEY.md §3.3), and compiled executables persist across restarts via the
AOT-serialized executable cache (serving/aotcache.py): warmup deserializes
previously compiled programs from disk instead of recompiling, so boot and
hot-swap rewarm are file reads, not compile storms (ISSUE 18; the same
remedy SURVEY.md §5.4's compilation cache gestures at, but for the LOADED
executable — no tracing, lowering, or linking on the warm path).
"""

from __future__ import annotations

import contextlib
import dataclasses
import logging
import os
import queue
import threading
import time
from concurrent.futures import ThreadPoolExecutor

import jax
import jax.numpy as jnp
import numpy as np

from ..graphdef import convert_pb
from ..ops import detection, quant
from ..ops.image import make_preprocess_fn, pad_to_canvas, rgb_to_yuv420_canvas
from ..parallel import mesh as mesh_lib
from ..utils.config import ModelConfig, ServerConfig
from ..utils.locks import named_condition, named_lock
from ..utils.tracing import canvas_side, stage
from . import aotcache
from .placement import parse_placement

log = logging.getLogger("tpu_serve.engine")

# Shared no-op guard for the (default) concurrent-dispatch path.
_NO_LOCK = contextlib.nullcontext()

# Part of every AOT cache key: bump when the serve-fn construction in
# _build_serve_fns changes semantics (preprocess composition, packing
# layout, postprocess), so executables cached by an older build can
# never serve a newer build's traffic. The config-derived key components
# cover operator-visible knobs; this covers the code itself.
SERVE_FN_VERSION = 2

# Bytes of one page of a ragged arena on a one-device replica: the unit in
# which an open batch's arena is copied to the device as its rows commit
# (RaggedSlab.settle). A multiple of 4, so that a page ships as the uint32
# words the unpack kernel reads. The tail a launch still copies is mostly
# the rows quantization's padding past the last image (up to bucket/8
# canvas rows), not the page, so a smaller page saves little there and
# costs a copy call and an operand of the unpack program a page.
PAGE_BYTES = 8 << 20

# A page's way to the device: not ready, handed to the replica's shipper,
# being put, on the device.
_PAGE_IDLE, _PAGE_QUEUED, _PAGE_SHIPPING, _PAGE_SHIPPED = range(4)


def _join_pages(pages):
    """An arena prefix from its pages, on the device (inside the unpack
    program): one read and one write of the prefix."""
    return pages[0] if len(pages) == 1 else jnp.concatenate(pages)


def page_sizes(nbytes: int) -> list[int]:
    """The pages an arena prefix of ``nbytes`` travels as, in arena order:
    whole pages, then the rest. One list per prefix length, so each
    compiled unpack variant keeps exactly one shape."""
    n = -(-nbytes // PAGE_BYTES)
    return [PAGE_BYTES] * (n - 1) + [nbytes - (n - 1) * PAGE_BYTES]


class StagingSlab:
    """One preallocated host staging buffer for a (canvas-row-shape,
    batch-bucket) pair.

    The request path's data-movement budget is exactly one row write per
    image (the native decoder writes the JPEG straight into its slot via
    :meth:`row`) and one host→device transfer of the slab — no
    ``np.stack``/``reshape``/``concatenate`` full-batch copies. On the
    packed wire the canvas rows and the 4-byte big-endian (h, w) trailers
    are VIEWS into one contiguous uint8 buffer, so writing a row lands the
    bytes directly in the array ``jax.device_put`` ships.

    Slot leasing: the batcher hands concurrent HTTP workers row views of
    one slab while the batch assembles. A slab may therefore only return
    to the pool when BOTH (a) every lease has been dropped (no thread can
    still be writing into a row) and (b) its batch's fetch completed (on
    CPU backends ``device_put`` may alias the numpy buffer). ``arm`` binds
    the pool-return callback for one acquire→dispatch→fetch cycle;
    ``add_lease``/``drop_lease``/``finish_fetch`` track the conjunction.
    """

    __slots__ = ("key", "bucket", "packed", "nbytes", "buf", "canvases",
                 "trailer", "hws", "total_bytes", "_lease_lock", "_leases",
                 "_fetch_done", "_idle_cb")

    def __init__(self, row_shape: tuple[int, ...], bucket: int, packed: bool):
        self.key = (tuple(row_shape), bucket)
        self.bucket = bucket
        self.packed = packed
        self.nbytes = int(np.prod(row_shape, dtype=np.int64))
        self._lease_lock = named_lock("slab.lease_lock")
        self._leases = 0
        self._fetch_done = True
        self._idle_cb = None
        if packed:
            self.buf = np.zeros((bucket, self.nbytes + 4), np.uint8)
            canv = self.buf[:, : self.nbytes].reshape(bucket, *row_shape)
            # Splitting the contiguous tail axis of a strided 2-D array is
            # always expressible as a view; if numpy ever copied here, row
            # writes would silently miss the wire buffer.
            assert np.shares_memory(canv, self.buf)
            self.canvases = canv
            self.trailer = self.buf[:, self.nbytes :]
            self.trailer[:] = (0, 1, 0, 1)  # hw=(1,1) until a row is written
            self.hws = None
            self.total_bytes = self.buf.nbytes
        else:
            self.buf = None
            self.canvases = np.zeros((bucket, *row_shape), np.uint8)
            self.hws = np.ones((bucket, 2), np.int32)
            self.trailer = None
            self.total_bytes = self.canvases.nbytes + self.hws.nbytes

    # ------------------------------------------------------------- slot API

    def row(self, i: int) -> np.ndarray:
        """Contiguous canvas view of slot ``i`` — the destination buffer a
        leasing decoder writes into (wire bytes → slab, no intermediate)."""
        return self.canvases[i]

    def write_hw(self, i: int, hw: tuple[int, int]):
        """Stamp slot ``i``'s valid (h, w) without touching its canvas —
        the slot-lease commit path, where the canvas bytes were already
        decoded in place via :meth:`row`."""
        h, w = int(hw[0]), int(hw[1])
        if self.packed:
            self.trailer[i, 0] = h >> 8
            self.trailer[i, 1] = h & 0xFF
            self.trailer[i, 2] = w >> 8
            self.trailer[i, 3] = w & 0xFF
        else:
            self.hws[i, 0] = h
            self.hws[i, 1] = w

    def arm(self, idle_cb):
        """Start one lease/dispatch/fetch cycle; ``idle_cb(slab)`` fires
        once every lease is dropped AND ``finish_fetch`` ran."""
        with self._lease_lock:
            self._leases = 0
            self._fetch_done = False
            self._idle_cb = idle_cb

    def add_lease(self):
        with self._lease_lock:
            self._leases += 1

    def drop_lease(self):
        self._maybe_idle(dec=True)

    def finish_fetch(self):
        self._maybe_idle(fetched=True)

    def _maybe_idle(self, dec: bool = False, fetched: bool = False):
        cb = None
        with self._lease_lock:
            if dec:
                self._leases -= 1
            if fetched:
                self._fetch_done = True
            if self._fetch_done and self._leases <= 0 and self._idle_cb is not None:
                cb = self._idle_cb
                self._idle_cb = None
        if cb is not None:  # outside the lock: cb takes the pool lock
            cb(self)

    def write_row(self, i: int, canvas: np.ndarray, hw: tuple[int, int]):
        """Stage one request: the single host copy its bytes ever make."""
        self.canvases[i] = canvas
        self.write_hw(i, hw)

    def write_rows(self, canvases: np.ndarray, hws: np.ndarray):
        """Stage an already-stacked batch (compat path for run_batch/bench)."""
        n = canvases.shape[0]
        self.canvases[:n] = canvases
        if self.packed:
            self.trailer[:n] = np.asarray(hws).astype(">u2").view(np.uint8).reshape(n, 4)
        else:
            self.hws[:n] = hws

    def pad_from(self, n: int):
        """Mark rows n..bucket as padding (hw = 1×1 — the resize reads one
        pixel). Stale canvas bytes in padding rows are never observable:
        every output consumer slices to the real batch size."""
        if self.packed:
            self.trailer[n:] = (0, 1, 0, 1)
        else:
            self.hws[n:] = 1


class RaggedSlab:
    """One host staging buffer for the RAGGED wire of a (canvas bucket,
    batch bucket) pair: a flat bump-allocated byte ARENA of tight decoded
    images (each occupies exactly h*w*3 bytes at native stride — no canvas
    padding, images pack back to back across row boundaries) plus an int32
    meta table ``[byte_offset, h, w, valid]`` per slot. Dispatch ships the
    arena's used prefix (quantized to bucket/8 canvas-row steps so the
    compiled shape count stays bounded) and the meta table; a jitted
    on-device unpack stage (:func:`..ops.image.unpack_ragged`) rebuilds
    each image's canvas bit-identically to the classic host-padded slab,
    so everything downstream — serve preprocess, model, cache semantics —
    is unchanged while mixed-size traffic stops shipping ~70% padding.

    Slot leasing is the same conjunction protocol as :class:`StagingSlab`
    (``arm``/``add_lease``/``drop_lease``/``finish_fetch``). Allocation is
    a bump cursor advanced only by the batch builder's thread (under the
    batcher cond), so :meth:`alloc` needs no lock of its own. A slot whose
    lease dies before commit keeps valid=0: unpack emits a zero canvas
    with hw=(1,1) — the classic hole semantics, one pixel the output
    consumers never observe (every result is sliced to the real batch).

    A ``paged`` arena (an engine whose replicas are one device each) is
    also cut into pages of :data:`PAGE_BYTES`. A page is ready once every
    byte of it is allocated and every slot that overlaps it is settled
    (committed, a hole, or force-expired: an expired row's bytes are
    don't-care, as its meta row says); :meth:`settle` names the pages a
    slot's settling makes ready, the replica's shipper copies them to the
    device while the batch is still open (:meth:`claim`, :meth:`landed`),
    and the launch takes what has landed and copies the rest
    (:meth:`take_pages`). ``replica`` is the replica the arena was bound to
    when its first page was handed over (None before that)."""

    is_ragged = True

    __slots__ = ("key", "bucket", "canvas_s", "row_bytes", "arena_bytes",
                 "buf", "meta", "used", "slots", "total_bytes",
                 "_lease_lock", "_leases", "_fetch_done", "_idle_cb",
                 "paged", "replica", "_gen", "_closed", "_ends",
                 "_unsettled", "_page_state", "_pages_d", "_page_t")

    def __init__(self, canvas_s: int, bucket: int, paged: bool = False):
        # key[0] = ("ragged", s) is a 2-tuple, so utils.tracing.canvas_side
        # reads the canvas bucket out of it exactly as it does for classic
        # (s, s, 3) row-shape keys — economics keying needs no branch, and
        # the key can never collide with a classic slab's in the shared
        # staging pool.
        self.key = (("ragged", int(canvas_s)), bucket)
        self.bucket = bucket
        self.canvas_s = int(canvas_s)
        self.row_bytes = self.canvas_s * self.canvas_s * 3
        self.arena_bytes = bucket * self.row_bytes
        self.buf = np.zeros(self.arena_bytes, np.uint8)
        self.meta = np.zeros((bucket, 4), np.int32)
        self.used = 0
        self.slots = 0
        self.total_bytes = self.buf.nbytes + self.meta.nbytes
        # A condition: the launch waits on it for pages being put.
        self._lease_lock = named_condition("slab.lease_lock")
        self._leases = 0
        self._fetch_done = True
        self._idle_cb = None
        self.paged = bool(paged)
        self._gen = 0
        self._reset_pages()

    def _reset_pages(self):
        """A new cycle's page table (under the lease lock, or unshared)."""
        n = -(-self.arena_bytes // PAGE_BYTES) if self.paged else 0
        self.replica = None
        self._closed = False
        self._ends: list[int] = []  # slot i's arena bytes end at _ends[i]
        self._unsettled = [0] * n   # slots overlapping the page, unsettled
        self._page_state = [_PAGE_IDLE] * n
        self._pages_d: list = [None] * n
        self._page_t = [0.0] * n    # when the page's copy started

    # ------------------------------------------------------------- slot API

    def alloc(self, need: int) -> tuple[int, np.ndarray] | None:
        """Bump-allocate ``need`` arena bytes for one image: (slot index,
        writable flat view), or None when the arena is out of slots or
        bytes (the builder seals and starts a new batch). No per-image
        alignment — packing tight is exactly where the win comes from."""
        if self.slots >= self.bucket or self.used + need > self.arena_bytes:
            return None
        i = self.slots
        off = self.used
        self.slots = i + 1
        self.used = off + need
        self.meta[i, 0] = off
        if self.paged:
            self._ends.append(off + need)
            for p in range(off // PAGE_BYTES, (off + need - 1) // PAGE_BYTES + 1):
                self._unsettled[p] += 1
        # h/w/valid stay 0 until write_hw: an abandoned lease is a hole.
        return i, self.buf[off : off + need]

    def settle(self, i: int) -> list[int]:
        """Slot ``i``'s lessee will write no more that counts: committed,
        released or force-expired (once a slot, under the batcher's
        condition, as :meth:`alloc`). Returns the pages this makes ready,
        marked handed over: every byte allocated, every overlapping slot
        settled. A ready page is whole and lies below ``used``, so no later
        slot can overlap it: each page is handed over at most once."""
        if not self.paged:
            return []
        off, end = int(self.meta[i, 0]), self._ends[i]
        ready = []
        for p in range(off // PAGE_BYTES, (end - 1) // PAGE_BYTES + 1):
            self._unsettled[p] -= 1
            if not self._unsettled[p] and (p + 1) * PAGE_BYTES <= self.used:
                ready.append(p)
        if ready:
            with self._lease_lock:
                if self._closed:
                    return []
                for p in ready:
                    self._page_state[p] = _PAGE_QUEUED
        return ready

    def claim(self, gen: int, pages) -> list[tuple[int, np.ndarray]]:
        """The shipper's half: of ``pages`` (handed over in cycle ``gen``),
        those still waiting, marked as being put, with their bytes."""
        with self._lease_lock:
            if gen != self._gen or self._closed:
                return []
            mine = [p for p in pages if self._page_state[p] == _PAGE_QUEUED]
            for p in mine:
                self._page_state[p] = _PAGE_SHIPPING
        return [(p, self.buf[p * PAGE_BYTES:(p + 1) * PAGE_BYTES]) for p in mine]

    def landed(self, gen: int, pages, arrays, t0: float) -> None:
        """The shipper put ``pages`` (``arrays``, their device copies; None
        if the put failed, and the launch copies them) from ``t0`` on."""
        with self._lease_lock:
            if gen == self._gen:
                for k, p in enumerate(pages):
                    if arrays is None:
                        self._page_state[p] = _PAGE_IDLE
                    else:
                        self._page_state[p] = _PAGE_SHIPPED
                        self._pages_d[p] = arrays[k]
                        self._page_t[p] = t0
            self._lease_lock.notify_all()

    def take_pages(self, n: int) -> dict[int, tuple]:
        """At launch (or release): hand nothing more to the shipper, wait
        for the pages it is putting, and take the first ``n`` pages' device
        copies, ``{page: (array, when its copy started)}``. The arena's
        device pages are the batch's from here: none stays on the slab."""
        with self._lease_lock:
            self._closed = True
            while _PAGE_SHIPPING in self._page_state:
                self._lease_lock.wait()
            got = {p: (self._pages_d[p], self._page_t[p]) for p in range(n)
                   if self._page_state[p] == _PAGE_SHIPPED}
            self._pages_d = [None] * len(self._pages_d)
        return got

    def write_hw(self, i: int, hw: tuple[int, int]):
        """Commit slot ``i``: stamp its decoded (h, w) and mark it valid —
        same commit signature as :meth:`StagingSlab.write_hw`, so the
        batcher's commit and hole-padding paths need no ragged branch."""
        self.meta[i, 1] = int(hw[0])
        self.meta[i, 2] = int(hw[1])
        self.meta[i, 3] = 1

    def rows_shipped(self, bucket: int) -> int:
        """Arena rows (canvas-row equivalents) a dispatch at compiled batch
        ``bucket`` actually ships: used bytes rounded up to q = max(1,
        bucket/8) rows, so at most ~8 wire shapes exist per (canvas,
        bucket) pair — the jit cache stays bounded while residual padding
        stays under one quantization step. ``bucket`` must be the DISPATCH
        bucket, not this slab's capacity (the batcher leases top-capacity
        slabs and dispatch re-buckets): warmup compiles exactly the
        (bucket, rows) variants this quantization yields for it. The clamp
        at ``bucket`` rows never cuts data: slot i ends within its first
        i + 1 rows."""
        q = max(1, bucket // 8)
        rows = (self.used + self.row_bytes - 1) // self.row_bytes
        rows = max(q, ((rows + q - 1) // q) * q)
        return min(bucket, rows)

    def arm(self, idle_cb):
        """Start one cycle (same contract as :meth:`StagingSlab.arm`) and
        reset the arena: cursors to zero, meta cleared — stale offsets from
        the previous batch must never alias a new batch's holes. A new
        cycle's page table too: a shipper still holding the last cycle's
        pages finds another ``_gen`` and keeps none."""
        with self._lease_lock:
            self._leases = 0
            self._fetch_done = False
            self._idle_cb = idle_cb
            self._gen += 1
            self._reset_pages()
        self.used = 0
        self.slots = 0
        self.meta[:] = 0

    def add_lease(self):
        with self._lease_lock:
            self._leases += 1

    def drop_lease(self):
        self._maybe_idle(dec=True)

    def finish_fetch(self):
        self._maybe_idle(fetched=True)

    def _maybe_idle(self, dec: bool = False, fetched: bool = False):
        cb = None
        with self._lease_lock:
            if dec:
                self._leases -= 1
            if fetched:
                self._fetch_done = True
            if self._fetch_done and self._leases <= 0 and self._idle_cb is not None:
                cb = self._idle_cb
                self._idle_cb = None
        if cb is not None:  # outside the lock: cb takes the pool lock
            cb(self)


def _batch_ids(rec: dict | None) -> dict:
    """The identities a batch's profiler annotations carry as stats, from
    the batcher's record of it (none for a dispatch outside the batcher:
    warm-up, run_batch, the DAG executor)."""
    return {} if rec is None else {"seq": rec["seq"], "rows": rec["rows"]}


class Flight:
    """One call's flight on its device, stamped (``time.monotonic()``) where
    each phase ends: ``t_h2d_done`` the inputs' copy landed, ``t_dev_start``
    the device turned to the call (the later of the copy's end and the
    previous call's ``t_ready``: a device runs its calls in order),
    ``t_ready`` the outputs are computed, before their copy to the host.
    ``late`` names the stamps that a thread took only after the event had
    happened: upper bounds, not times. The stamps are written into ``rec``
    too, the batcher's record of the batch, where there is one."""

    __slots__ = ("rec", "prev", "ann", "t_h2d_done", "t_dev_start",
                 "t_ready", "late")

    def __init__(self, rec: dict | None, ann):
        self.rec, self.ann, self.prev = rec, ann, None
        self.t_h2d_done = self.t_dev_start = self.t_ready = None
        self.late: tuple[str, ...] = ()

    @property
    def device_s(self) -> float:
        """The device phase: the device's own time on the call."""
        return 0.0 if self.t_ready is None else self.t_ready - self.t_dev_start

    def _set(self, key: str, t: float, late: bool = False):
        setattr(self, key, t)
        if late:
            self.late += (key,)
        if self.rec is not None:
            self.rec[key] = t
            self.rec["late"] = self.late


class FlightLog:
    """The calls of one device stream in the order they were enqueued, and
    the threads that stamp their flights: a few waiter threads of this
    log's own take each copy as it is handed over and wait for the call's
    inputs to land (copies of consecutive calls overlap and may land out of
    order); the thread that fetches a call waits for its outputs to be
    computed before it converts them (:meth:`land`). Each was waiting
    already when its event happened, so a stamp is the event's time; one
    whose event had happened when its thread turned to it is ``late``.

    The copy's profiler annotation ``twd.h2d_flight`` (stats ``seq``,
    ``rows``, ``h2d_bytes``) opens on the launch thread as the ``device_put``
    starts and closes where ``t_h2d_done`` is stamped. Stamps are taken
    under ``lock`` (the engine's route lock), so that whichever thread
    stamps first stamps once."""

    # Copies waited on at once (the photos cell has had three in flight).
    WAITERS = 4

    def __init__(self, lock, name: str = "h2d-watch"):
        self._lock = lock
        self._last: Flight | None = None
        self._copies: queue.Queue = queue.Queue()
        self._waiters = [threading.Thread(target=self._watch, name=name,
                                          daemon=True)
                         for _ in range(self.WAITERS)]
        for t in self._waiters:
            t.start()

    def start(self, rec: dict | None, label: str, nbytes: int) -> Flight:
        """A dispatch is about to put ``nbytes`` of inputs: its flight."""
        ann = stage(None, "h2d_flight", label, **_batch_ids(rec),
                    h2d_bytes=int(nbytes))
        return Flight(rec, ann.__enter__())

    def copying(self, f: Flight, bufs) -> None:
        """Hand the arrays ``device_put`` just returned to a waiter."""
        self._copies.put((f, tuple(bufs)))

    def close(self) -> None:
        for _ in self._waiters:
            self._copies.put(None)
        for t in self._waiters:
            t.join(timeout=5)

    def enqueued(self, f: Flight) -> None:
        """The call is on the device's queue, behind the one before it
        (called outside the replica's dispatch guard: the route lock ranks
        above it)."""
        with self._lock:
            f.prev, self._last = self._last, f

    def land(self, f: Flight, outs) -> None:
        """Wait until the call's outputs are computed and stamp it."""
        leaves = jax.tree.leaves(outs)
        late = all(o.is_ready() for o in leaves)
        try:
            for o in leaves:
                o.block_until_ready()
        finally:
            with self._lock:
                self._land_locked(f, late)

    def _copied_locked(self, f: Flight, late: bool):
        f.ann.__exit__(None, None, None)
        f._set("t_h2d_done", f.ann.t1, late)

    def _land_locked(self, f: Flight, late: bool):
        # The device runs its calls in order: when this call's outputs are
        # computed, so are those of the calls before it, and their copies
        # have landed. Whatever of theirs is unstamped yet is stamped now.
        chain = []
        while f is not None and f.t_ready is None:
            chain.append(f)
            f = f.prev
        for g in chain:
            if g.t_h2d_done is None:
                self._copied_locked(g, True)
        now = time.monotonic()
        for i, g in enumerate(reversed(chain)):
            behind = i < len(chain) - 1
            prev = g.prev
            g._set("t_dev_start", g.t_h2d_done if prev is None
                   else max(g.t_h2d_done, prev.t_ready))
            g._set("t_ready", now, late or behind)
            g.prev = None

    def _watch(self):
        while True:
            item = self._copies.get()
            if item is None:
                return
            self._wait_copy(*item)
            del item  # the inputs' device buffers go with it

    def _wait_copy(self, f: Flight, bufs: tuple):
        late = True
        try:
            late = all(b.is_ready() for b in bufs)
            for b in bufs:
                b.block_until_ready()
        except Exception:
            pass  # a copy that failed fails its call: the fetch says so
        with self._lock:
            if f.t_h2d_done is None:
                self._copied_locked(f, late)


class PageShipper:
    """The early copies of one replica's ragged arenas: a thread of its own
    puts the pages that filled while their batch was open
    (:meth:`RaggedSlab.settle`), so that neither a decode worker nor the
    batcher's lock ever waits for a copy. Each put lies under the profiler
    annotation ``twd.h2d_early`` (stats ``seq``, ``pages``, ``bytes``).

    Pages go only to a replica of one device, where there is no XLA:CPU
    dispatch guard to take (``_Replica.serialize`` needs several)."""

    def __init__(self, sharding, name: str):
        self._sharding = sharding
        self._q: queue.Queue = queue.Queue()
        self._thread = threading.Thread(target=self._run, name=name, daemon=True)
        self._thread.start()

    def put(self, slab: RaggedSlab, pages, seq, words: bool) -> None:
        """Queue ``pages`` of ``slab`` (its batch ``seq``; as uint32 words
        where the unpack is the kernel). Never blocks."""
        self._q.put((slab, slab._gen, tuple(pages), seq, words))

    def close(self) -> None:
        self._q.put(None)
        self._thread.join(timeout=5)

    def _run(self):
        while True:
            item = self._q.get()
            if item is None:
                return
            self._ship(*item)
            del item  # the slab goes with it

    def _ship(self, slab: RaggedSlab, gen: int, pages, seq, words: bool):
        claimed = slab.claim(gen, pages)
        if not claimed:
            return
        mine = [p for p, _ in claimed]
        bufs = [v.view(np.uint32) if words else v for _, v in claimed]
        t0 = time.monotonic()
        arrays = None
        try:
            with stage(None, "h2d_early", f"c{slab.canvas_s}", seq=seq,
                       pages=len(mine), bytes=len(mine) * PAGE_BYTES):
                arrays = jax.device_put(bufs, self._sharding)
        except Exception:
            log.exception("early copy of %d page(s) failed; the launch "
                          "copies them", len(mine))
        slab.landed(gen, mine, arrays, t0)


class _DeviceBatch:
    """Slab-shaped handle for :meth:`InferenceEngine.dispatch_device` —
    a DEVICE-RESIDENT batch (DAG glue output) that never had a host
    staging slab. Carries just what the shared fetch/accounting path
    reads off a slab: the (row-shape, bucket) key the economics cell is
    derived from, the wire byte count, and a no-op pool-return (there is
    nothing to pool — the device buffers free with the jax arrays)."""

    is_ragged = False

    __slots__ = ("key", "bucket", "total_bytes")

    def __init__(self, row_shape: tuple[int, ...], bucket: int,
                 total_bytes: int):
        self.key = (tuple(row_shape), bucket)
        self.bucket = bucket
        self.total_bytes = int(total_bytes)

    def finish_fetch(self):
        pass


class _Replica:
    """One independent dispatch stream of an engine's placement: a device
    subset (its own submesh) holding a full copy of the params, its own
    compiled executables, its own XLA:CPU serialization guard, and its own
    in-flight/busy accounting. With placement "shard" there is exactly one
    replica spanning the whole mesh — the historical engine, unchanged."""

    __slots__ = ("index", "mesh", "params", "serve", "exe", "data_sharding",
                 "replicated", "dispatch_guard", "serialize",
                 "dispatches_total", "dispatches_inflight",
                 "slab_bytes_inflight", "busy_s", "econ", "flights",
                 "shipper", "param_device_ids", "param_bytes")

    def __init__(self, index: int, mesh):
        self.index = index
        self.mesh = mesh
        self.params = None
        self.param_device_ids: list[int] = []
        self.param_bytes = 0
        self.serve = None
        # AOT-compiled serve executables keyed ("serve", canvas_s, batch
        # bucket) — populated by warmup (deserialize-from-cache or eager
        # compile); dispatch falls back to the lazy `serve` jit wrapper
        # for shapes warmup never saw. Plain dict: single-key get/set is
        # GIL-atomic, and warmup's thread pool only ever ADDS entries.
        self.exe: dict[tuple, object] = {}
        self.data_sharding = mesh_lib.data_sharding(mesh)
        self.replicated = mesh_lib.replicated(mesh)
        # XLA:CPU runs sharded programs on the caller's thread against one
        # shared virtual-device pool, so two multi-device dispatches from
        # different threads into the SAME replica can interleave their
        # per-device partitions and deadlock the collective rendezvous
        # (PR 5's find). The guard is per REPLICA: disjoint device sets
        # rendezvous independently (measured safe concurrently on this
        # backend), and single-device replicas run no collectives at all —
        # so replicated placement keeps dispatch concurrency ~N× even on
        # the CPU test mesh. Real accelerators never take the guard.
        self.serialize = (
            jax.default_backend() == "cpu" and mesh.devices.size > 1
        )
        self.dispatch_guard = named_lock("engine.replica_dispatch_lock")
        self.dispatches_total = 0
        self.dispatches_inflight = 0
        self.slab_bytes_inflight = 0
        # Cumulative device-phase seconds (Flight.device_s): per-replica
        # busy attribution for /stats. The device runs its calls one after
        # another, so a window's delta never passes its wall clock.
        self.busy_s = 0.0
        # Device-economics counters, keyed (canvas bucket, batch bucket):
        # [batches, rows staged, rows dispatched (= bucket × batches),
        # cumulative device-phase seconds, tight rows]. The measured half of the
        # roofline attribution (serving/costmodel.py supplies the analytic
        # half); bounded by the compiled bucket grid, so it can never grow
        # past len(canvas_buckets) × len(batch_buckets) entries.
        self.econ: dict[tuple[int, int], list] = {}
        # This stream's calls in enqueue order, stamped where each phase of
        # their flight ends (InferenceEngine._flights makes it on first use).
        self.flights: FlightLog | None = None
        # Puts ragged arenas' pages while their batches are open
        # (InferenceEngine._shipper makes it on first use).
        self.shipper: PageShipper | None = None


class InferenceEngine:
    """Loads one frozen graph and serves batches of decoded images across
    its placement's replicas (placement.py): per-replica params copies and
    executables, with dispatch routed round-robin/least-loaded unless the
    caller pins a replica."""

    # The batcher passes request spans to dispatch_staged(spans=...) only
    # when this is set — staging-API fakes/embedders with the plain
    # two-argument signature keep working unchanged.
    supports_span_tracing = True
    # Slabs from acquire_staging expose the slot-lease API (row views,
    # write_hw, lease refcounting) — the batcher's decode-into-slab path is
    # enabled only when this is set, so staging-API fakes without it keep
    # the write_row-per-request path.
    supports_slot_lease = True
    # dispatch_staged/dispatch_batch accept replica= and the engine exposes
    # num_replicas/replica_loads/route_replica — the batcher routes sealed
    # batches across replicas only when this is set, so fakes/embedders
    # with the plain signatures keep working unchanged.
    supports_replica_routing = True
    # Class defaults, so engines built without __init__ by the tests have
    # them too: the loaded ConvertedModel, and what it counts a call (its
    # counter_names).
    model = None
    counter_names: tuple[str, ...] = ()
    # How many serve calls the device's memory holds at once beside the
    # weights, found at warm-up from the compiled programs' own account
    # (_calls_that_fit); None where that is unknown, and for no ceiling but
    # the batcher's per-bucket pipeline depth.
    max_calls_in_flight: int | None = None
    # Whether ragged arenas ship by pages (set in __init__).
    _paged = False

    def __init__(self, cfg: ServerConfig, mesh=None):
        # Ragged-wire gating: tight-arena packing exists only for the rgb
        # wire (yuv420's chroma-plane canvas has no tight row layout), and
        # it subsumes packed_io's single-buffer trick — ragged dispatch
        # already ships exactly one arena + one small meta table, and the
        # device-side unpack hands the serve fn plain (canvases, hws).
        self.ragged = bool(cfg.ragged and cfg.wire_format == "rgb")
        if cfg.ragged and not self.ragged:
            log.warning(
                "ragged packing requires wire_format='rgb' (got %r); "
                "serving the classic host-padded wire", cfg.wire_format,
            )
        if self.ragged and cfg.packed_io:
            cfg = dataclasses.replace(cfg, packed_io=False)
        self.cfg = cfg
        self.model_cfg: ModelConfig = cfg.model
        self.mesh = mesh if mesh is not None else mesh_lib.build_mesh()
        # The roofline denominators, resolved at build: a TPU whose
        # device_kind is not in costmodel.DEVICE_PEAKS raises here, not as
        # a zero peak under a device-metric name at the first scrape. On
        # the CPU dev backend the peak is CALIBRATED once per process (~1 s
        # of jitted matmul + stream timing), which /stats must never pay.
        from . import costmodel

        t0 = time.perf_counter()
        peak = costmodel.backend_peak(self.model_cfg.dtype)
        log.info("econ peak %s (%.2fs, one-time per process and dtype)",
                 peak["source"], time.perf_counter() - t0)
        # Raw-speed tier: fused depthwise chain (ops/depthwise.py — dwconv +
        # folded BN + relu6 as one op). "auto" fuses the quantized tier only
        # (int8's build-time parity gate guards the numerics); "on"/"off"
        # force it — the bench A/B knob. Native-only: a frozen .pb graph has
        # no flax module to rebuild.
        fused_knob = getattr(self.model_cfg, "fused_dw", "auto")
        self._fused_dw = (
            self.model_cfg.source == "native"
            and (fused_knob == "on"
                 or (fused_knob == "auto" and self.model_cfg.dtype == "int8"))
        )
        if fused_knob == "on" and self.model_cfg.source != "native":
            log.warning(
                "fused_dw='on' ignored for source='pb' (%s): fusion rebuilds "
                "the flax module, which a frozen graph does not have",
                self.model_cfg.name,
            )
        t0 = time.perf_counter()
        if self.model_cfg.source == "native":
            from .. import models as zoo
            from ..models.adapter import native_converted

            # Stem↔preprocess handshake: on the yuv420 wire the matmul
            # resize can emit the stem's space-to-depth cell layout straight
            # from its einsums — no materialized RGB canvas, no fold
            # transpose (ops/image.py, ops/stem.py). Gated by the spec: the
            # even-extent cell convention must be exact for this stem.
            h0, w0 = self.model_cfg.input_size
            self._s2d_handshake = (
                cfg.wire_format == "yuv420"
                and zoo.get(self.model_cfg.name).s2d_ok(h0, w0)
            )
            self.model = native_converted(
                self.model_cfg.name,
                num_classes=self.model_cfg.zoo_classes,
                width=self.model_cfg.zoo_width,
                # the serving preprocess resizes to input_size, so the
                # detector's anchor grid must be derived from the same value
                input_size=self.model_cfg.input_size[0],
                ckpt_path=self.model_cfg.ckpt_path,
                input_format="s2d" if self._s2d_handshake else "nhwc",
                fused_dw=self._fused_dw,
                decoder=self.model_cfg.decoder,
                topk=self.model_cfg.topk,
            )
        else:
            self.model = convert_pb(
                self.model_cfg.pb_path,
                outputs=self.model_cfg.output_names,
                inputs=[self.model_cfg.input_name] if self.model_cfg.input_name else None,
            )
            # Same stem↔preprocess handshake as the native zoo, via the
            # converter's input-format rewrite: when the frozen graph's stem
            # matches the s2d pattern and the cell convention is exact at
            # the serving size, swap in the cells-consuming variant fn.
            h0, w0 = self.model_cfg.input_size
            self._s2d_handshake = bool(
                cfg.wire_format == "yuv420"
                and self.model.s2d_stem is not None
                and self.model.s2d_stem.supports(h0, w0)
            )
            if self._s2d_handshake:
                self.model.fn = self.model.s2d_stem.build(h0, w0)
                # Keep input_specs truthful (the native path does the same
                # in models/adapter.py): fn now consumes cells, not NHWC.
                spec0 = self.model.input_specs[0]
                spec0.shape = [None, (h0 + 1) // 2, (w0 + 1) // 2, 12]
                log.info(
                    "s2d input rewrite active: stem conv %s consumes the "
                    "preprocess cell layout", self.model.s2d_stem.conv_name,
                )
        if self.model.from_canvases and (
                cfg.wire_format != "rgb" or self.model_cfg.dtype == "int8"):
            raise ValueError(
                f"model '{self.model_cfg.name}' takes patches of rgb canvases, "
                "in float32 or bfloat16")
        self.counter_names = self.model.counter_names
        log.info(
            "loaded %s (%s): %d params tensors, inputs=%s outputs=%s (%.1fs)",
            self.model_cfg.pb_path or self.model_cfg.name,
            self.model_cfg.source,
            len(self.model.params),
            self.model.input_names,
            self.model.output_names,
            time.perf_counter() - t0,
        )

        # Serving dtype variant. int8 stores per-channel-quantized kernels
        # (ops/quant.py) and COMPUTES in bf16 — the int8 leaves dequantize on
        # the fly inside the jitted serve fn, so HBM param traffic is 1 byte
        # per weight while the matmuls still ride the bf16 units.
        self._quantized = self.model_cfg.dtype == "int8"
        dtype = jnp.float32 if self.model_cfg.dtype == "float32" else jnp.bfloat16
        self._dtype = dtype
        if self._quantized:
            params = quant.quantize_params(self.model.params, dtype)
        else:
            # float32 leaves take the served dtype; so do bfloat16 ones (a
            # leaf export holds the served dtype already, or is served in
            # float32).
            params = {
                k: v.astype(dtype)
                if v.dtype in (np.float32, jnp.bfloat16) and v.dtype != dtype else v
                for k, v in self.model.params.items()
            }
            if self.model.from_canvases:
                # The device copy is the one that serves, and parity_check,
                # the one reader of the host's after placement, feeds a
                # resized square: the host's 2 bytes a parameter (10 GB at
                # the published widths) go back.
                self.model.params = {}
        # Golden numerical-parity gate: a quantized variant must prove itself
        # against the f32 reference BEFORE any device placement — a failing
        # gate parks the registry load in FAILED instead of serving garbage.
        self.parity: dict | None = None
        if self._quantized:
            self.parity = self.parity_check()
            if not self.parity.get("pass"):
                raise RuntimeError(
                    f"numerical-parity gate failed for {self.model_cfg.name} "
                    f"dtype={self.model_cfg.dtype}: {self.parity}"
                )
        # Placement: how this model occupies the mesh. "shard" (default) is
        # one replica over every device — the historical engine; "replicas=N"
        # splits the mesh into N disjoint groups, each with a full params
        # copy and its own executables/dispatch stream.
        self.placement = parse_placement(
            getattr(self.model_cfg, "placement", None), self.mesh
        )
        self.num_replicas = self.placement.replicas
        self._replicas = [
            _Replica(i, m) for i, m in enumerate(self.placement.meshes)
        ]
        for rep in self._replicas:
            rep.params = jax.device_put(params, rep.replicated)
            # Where the copy really landed, read back from the arrays (not
            # from the mesh it was asked for): /stats shows a placement
            # that put every replica's params on the first device.
            leaves = jax.tree.leaves(rep.params)
            rep.param_device_ids = sorted(
                {int(d.id) for leaf in leaves for d in leaf.sharding.device_set})
            rep.param_bytes = int(sum(leaf.nbytes for leaf in leaves))
        # Replica-routing state: the round-robin cursor plus every replica's
        # in-flight/busy counters live under this one small lock — taken
        # briefly, never across device work or any other lock.
        self._route_lock = named_lock("engine.route_lock")
        self._rr = 0
        # Device→host traffic, in bytes, actually converted by this
        # engine's fetch paths (fetch_outputs' full-buffer conversions
        # plus any partial row fetches a DAG executor accounts via
        # note_d2h) — the measured side of the pipeline bench's
        # D2H-bytes/image comparison.
        self._d2h_bytes = 0
        rep0 = self._replicas[0]
        # Replica-0 handles under the historical names: bench.py's scan
        # path and single-stream embedders read these.
        self._params = rep0.params
        self._data_sharding = rep0.data_sharding
        self._replicated = rep0.replicated

        # Batches shard over ONE replica's submesh, so the bucket ladder is
        # sized per replica (8 replicas on 8 chips serve batch multiples of
        # 1, not 8 — exactly the point of replicating a small model).
        self.batch_multiple = mesh_lib.batch_multiple(rep0.mesh)
        buckets = cfg.batch_buckets or self._default_batch_buckets(cfg.max_batch)
        self.batch_buckets = tuple(sorted(set(buckets)))
        # Explicit batch_buckets are authoritative: the batcher must never
        # assemble more requests than the top compiled shape can hold (a batch
        # above the top bucket would pay a request-time compile — the stall
        # warmup exists to prevent). Clamp the effective max_batch instead of
        # rejecting the config; callers size the batcher from engine.max_batch.
        self.max_batch = min(cfg.max_batch, self.batch_buckets[-1])
        if self.max_batch < cfg.max_batch:
            # warning, not info: this overrides explicit operator config and
            # caps batch assembly — it must be visible at default log levels.
            log.warning(
                "max_batch clamped %d -> %d (top batch bucket)",
                cfg.max_batch, self.max_batch,
            )

        self._build_serve_fns()
        self._serve = rep0.serve

        # Staging-slab pool: free slabs per (row-shape, bucket) key. Slabs in
        # flight are owned by their batch's handle and return to the pool when
        # fetch_outputs completes — never earlier, because on CPU backends
        # jax.device_put may alias the numpy buffer, so overwriting a slab
        # whose batch is still executing would corrupt it.
        self._staging_pool: dict[tuple, list[StagingSlab]] = {}
        self._staging_lock = named_lock("engine.staging_lock")
        self._staging_cap = max(2, getattr(cfg, "staging_slabs", 6))
        # Reuse telemetry: lifetime acquisitions and how many of them had
        # to allocate (reuse share = 1 - allocs / acquires over a window).
        self._staging_acquires = 0
        self._staging_allocs = 0
        # Byte budget across POOLED (idle) slabs: staging_pool_bytes PLUS
        # the bytes that are out with batches right now (acquired and not
        # yet returned: open in a builder, sealed, in flight, or held by a
        # straggling lease). Traffic that keeps k slabs of a shape out may
        # keep as many idle, so a returned arena is the next builder's and
        # is not unmapped and mapped again; when the last slab out comes
        # back the budget is the floor again, so an idle server and the end
        # of warmup (which touches every (canvas, batch) bucket pair) hold
        # no more than staging_pool_bytes. LRU keys are evicted first.
        self._staging_budget = int(getattr(cfg, "staging_pool_bytes", 256 << 20))
        self._staging_pool_nbytes = 0
        self._staging_out = 0
        self._staging_out_nbytes = 0
        self._staging_last_use: dict[tuple, float] = {}

        # Ragged-wire state: pooled arenas ride the SAME staging pool (a
        # ("ragged", s) key can never collide with a classic row-shape
        # tuple); the per-(replica, canvas, bucket, rows) jitted unpack
        # wrappers live here. engine.ragged_lock is a pure-dict leaf —
        # wrapper construction under it is cheap jax.jit() plumbing, and
        # the compile happens at first CALL, outside any lock.
        self._ragged_fns: dict[tuple, tuple] = {}
        self._ragged_lock = named_lock("engine.ragged_lock")
        # Arenas ship by pages as their rows commit (RaggedSlab.settle)
        # where every replica is one device, the case the unpack kernel
        # asks for too; a replica over several devices copies an arena's
        # prefix in one put at launch.
        self._paged = self.ragged and all(
            int(rep.mesh.devices.size) == 1 for rep in self._replicas)

        # AOT executable cache (serving/aotcache.py, ISSUE 18): warmup
        # deserializes previously compiled executables from disk instead
        # of recompiling, so boot and hot-swap rewarm become file reads.
        # None = disabled (every shape compiles, exactly the historical
        # path). Never load-bearing for correctness: a corrupt or
        # mismatched entry degrades to recompile inside the cache.
        self._aot = aotcache.AotCache.from_config(cfg)

    # ---------------------------------------------------------------- build

    def _default_batch_buckets(self, max_batch: int) -> tuple[int, ...]:
        m = self.batch_multiple
        # Every bucket must shard evenly over the mesh, so the top bucket is
        # max_batch rounded UP to a multiple of the mesh size.
        top = max(m, ((max_batch + m - 1) // m) * m)
        buckets = []
        b = m
        while b < top:
            buckets.append(b)
            b *= 2
        buckets.append(top)
        return tuple(buckets)

    def max_rows(self, canvas_s: int) -> int:
        """The most rows one call may hold at this canvas bucket: the top
        batch bucket, or, where the model states its own ceiling
        (``ConvertedModel.max_rows``: a token decoder's is in token slots),
        the largest batch bucket within it; never less than the smallest."""
        model_max_rows = getattr(self.model, "max_rows", None)
        if model_max_rows is None:
            return self.batch_buckets[-1]
        most = min(model_max_rows(canvas_s), self.max_batch)
        fits = [b for b in self.batch_buckets if b <= most]
        return fits[-1] if fits else self.batch_buckets[0]

    def canvas_shape(self, batch: int, s: int) -> tuple[int, ...]:
        """Host-staged canvas batch shape for one (batch, canvas-bucket)."""
        if self.cfg.wire_format == "yuv420":
            return (batch, s * 3 // 2, s)
        return (batch, s, s, 3)

    def packed_shape(self, batch: int, s: int) -> tuple[int, int]:
        """Wire shape of one packed batch: flattened canvas bytes + the
        4-byte big-endian (h, w) trailer per image. The single source of
        truth for the packed layout — dispatch_batch builds it, serve_packed
        reshapes it back, bench.py lowers against it."""
        shape = self.canvas_shape(batch, s)
        return (batch, int(np.prod(shape[1:], dtype=np.int64)) + 4)

    def _make_preprocess(self, h: int, w: int, mesh):
        """Resolve the configured resize path to a preprocess callable for
        one replica's ``mesh`` (only the pallas shard_map wrapper embeds
        it; the other resize paths are mesh-free).

        resize="pallas" is the Mosaic kernel on a TPU and the Pallas
        interpreter on the CPU backend (tests, dev) — chosen by platform,
        never by a trial compile: if Mosaic refuses the kernel at a shape
        this engine serves, warmup raises with the compiler's message.
        """
        s2d = getattr(self, "_s2d_handshake", False)
        if self.cfg.resize == "pallas":
            from jax.sharding import PartitionSpec as P

            from ..ops.pallas_preprocess import preprocess_i420
            from ..ops.stem import pack_s2d

            interpret = jax.default_backend() == "cpu"
            norm = self.model_cfg.preprocess

            def run_kernel(canvases, hws):
                out = preprocess_i420(canvases, hws, h, w, norm, interpret=interpret)
                # The kernel emits NHWC; fold to cells when the model was
                # built for the s2d handshake (cheap next to the kernel).
                return pack_s2d(out) if s2d else out

            if mesh.devices.size > 1:
                # A pallas_call is a custom call with no GSPMD partitioning
                # rules — under the sharded serve jit it must be explicitly
                # mapped per-shard or the compiler would gather the batch.
                return jax.shard_map(
                    run_kernel,
                    mesh=mesh,
                    in_specs=(P("data"), P("data")),
                    out_specs=P("data"),
                    check_vma=False,
                )
            return run_kernel
        return make_preprocess_fn(
            h,
            w,
            self.model_cfg.preprocess,
            wire=self.cfg.wire_format,
            resize=self.cfg.resize,
            s2d=s2d,
        )

    def _build_serve_fns(self):
        """Trace the serve computation once, then bind one jitted wrapper
        per replica (each replica's in_shardings live on its own submesh,
        so each compiles/caches its own executables against its own device
        set — the per-replica dispatch streams replicated placement is
        made of)."""
        h, w = self.model_cfg.input_size
        model_fn = self.model.fn
        dtype = self._dtype
        task = self.model_cfg.task

        policy = None if dtype == jnp.float32 else dtype
        topk = self.model_cfg.topk
        quantized = self._quantized

        if self.model.from_canvases:
            def serve(params, canvases, hws):
                # The model's whole answer; wrapped for the name alone: a
                # recording and its readers know the program as jit_serve.
                return model_fn(params, canvases, hws)

            self._serve_raw = serve
            for rep in self._replicas:
                rep.serve = jax.jit(
                    serve, in_shardings=(rep.replicated, rep.data_sharding,
                                         rep.data_sharding))
            return

        def make_serve(preprocess):
            def serve(params, canvases, hws):
                if quantized:
                    # Dequant-on-the-fly: int8 leaves × their per-channel
                    # scales → bf16, traced INSIDE the jit so XLA fuses the
                    # expansion into each kernel's first use (HBM reads stay
                    # 1 byte/weight; scale leaves never reach model_fn).
                    params = quant.dequantize_tree(params, dtype)
                # named_scope: each op's metadata carries its phase
                # (resize / forward / topk); the module keeps its name.
                with jax.named_scope("resize"):
                    x = preprocess(canvases, hws).astype(dtype)
                with jax.named_scope("forward"):
                    outs = model_fn(params, x, float_dtype=policy)
                if task == "classify":
                    # Top-k on device: the host fetches k (score, index)
                    # pairs per image instead of the full class vector —
                    # postprocess belongs on the TPU, and device→host bytes
                    # are the scarce resource. Clamped at trace time: a
                    # 4-class fine-tune with the default topk=5 must serve,
                    # not crash on the first request.
                    with jax.named_scope("topk"):
                        probs = outs[0].astype(jnp.float32)
                        scores, idx = jax.lax.top_k(
                            probs, min(topk, probs.shape[-1]))
                        return (scores, idx.astype(jnp.int32))
                if task == "detect":
                    by_name = dict(zip(self.model.output_names, outs))
                    boxes = jax.vmap(detection.decode_boxes, in_axes=(0, None))(
                        by_name["raw_boxes"].astype(jnp.float32),
                        by_name["anchors"][0].astype(jnp.float32)
                        if by_name["anchors"].ndim == 3
                        else by_name["anchors"].astype(jnp.float32),
                    )
                    scores = jax.nn.sigmoid(by_name["raw_scores"].astype(jnp.float32))[..., 1:]
                    return detection.multiclass_nms(boxes, scores)  # nested jit inlines
                return tuple(o.astype(jnp.float32) for o in outs)

            return serve

        # The preprocess is per REPLICA only when it embeds a mesh (the
        # pallas shard_map wrapper); otherwise one closure serves them all.
        def serve_for(rep):
            if rep.index == 0:
                return serve0
            return make_serve(self._make_preprocess(h, w, rep.mesh))

        serve0 = make_serve(self._make_preprocess(h, w, self._replicas[0].mesh))
        # Raw (unjitted) serve kept for callers that embed the computation in
        # a larger jitted program — bench.py wraps it in a lax.scan so one
        # dispatch amortizes many batches (the device-resident measurement).
        # Replica 0's preprocess; embedding callers are single-stream.
        self._serve_raw = serve0

        if not self.cfg.packed_io:
            for rep in self._replicas:
                rep.serve = jax.jit(
                    serve_for(rep),
                    in_shardings=(rep.replicated, rep.data_sharding,
                                  rep.data_sharding),
                )
            return

        # Output layout for the packed path: tail shapes/dtypes are batch-
        # independent, so one abstract trace on the smallest bucket pins them.
        b0, s0 = self.batch_buckets[0], self.cfg.canvas_buckets[0]
        p_avals = jax.tree.map(
            lambda v: jax.ShapeDtypeStruct(v.shape, v.dtype), self._params
        )
        out_avals = jax.eval_shape(
            serve0,
            p_avals,
            jax.ShapeDtypeStruct(self.canvas_shape(b0, s0), jnp.uint8),
            jax.ShapeDtypeStruct((b0, 2), jnp.int32),
        )
        self._out_tails = [
            (a.shape[1:], np.dtype(a.dtype)) for a in jax.tree.leaves(out_avals)
        ]

        wire = self.cfg.wire_format

        def make_packed(serve):
            def serve_packed(params, buf):
                # One uint8 buffer per batch: [canvas bytes..., h_hi, h_lo,
                # w_hi, w_lo]. The request path ships ONE array and fetches
                # ONE array (3 host↔device hops instead of 5 at batch 1);
                # what a hop costs over the host's own PCIe is not measured
                # (ROADMAP D2).
                b = buf.shape[0]
                nbytes = buf.shape[1] - 4
                if wire == "yuv420":
                    s = int(round((nbytes * 2 / 3) ** 0.5))
                    canv = buf[:, :nbytes].reshape(b, s * 3 // 2, s)
                else:
                    s = int(round((nbytes / 3) ** 0.5))
                    canv = buf[:, :nbytes].reshape(b, s, s, 3)
                hwb = buf[:, nbytes:].astype(jnp.int32)
                hws = jnp.stack(
                    [hwb[:, 0] * 256 + hwb[:, 1], hwb[:, 2] * 256 + hwb[:, 3]], axis=1
                )
                outs = serve(params, canv, hws)
                flat = [
                    o.astype(jnp.float32).reshape(b, -1) for o in jax.tree.leaves(outs)
                ]
                return jnp.concatenate(flat, axis=1)

            return serve_packed

        # The packed buffer is not donated: the replica's FlightLog waits on
        # it for the copy's end, and a donated buffer is deleted by the call
        # it feeds.
        for rep in self._replicas:
            rep.serve = jax.jit(
                make_packed(serve_for(rep)),
                in_shardings=(rep.replicated, rep.data_sharding),
            )

    # ------------------------------------------------------- AOT executables

    def _aot_key(self, rep: _Replica, kind: str, canvas_s: int, bucket: int,
                 rows: int | None = None, extra: dict | None = None) -> dict:
        """The full invalidation surface of one executable, as a
        JSON-plain dict (aotcache digests it): anything that could make
        a cached program wrong for this process must appear here, so a
        stale or foreign entry is simply never found."""
        import jaxlib

        mc = self.model_cfg
        devices = rep.mesh.devices
        key = {
            "v": aotcache.FORMAT_VERSION,
            "serve_fn": SERVE_FN_VERSION,
            "jax": jax.__version__,
            "jaxlib": jaxlib.__version__,
            "backend": jax.default_backend(),
            "device_kind": str(devices.flat[0].device_kind),
            # Serialized executables bind to their exact device
            # assignment, so the submesh topology AND the concrete
            # device ids are key components (replica 1's entry must
            # never load for replica 0).
            "mesh_shape": list(devices.shape),
            "device_ids": [int(d.id) for d in devices.flat],
            "model": mc.name,
            "source": mc.source,
            "dtype": mc.dtype,
            "fused_dw": bool(self._fused_dw),
            "input_size": list(mc.input_size),
            "topk": mc.topk,
            "task": mc.task,
            "decoder": mc.decoder,
            "preprocess": mc.preprocess,
            "zoo_width": mc.zoo_width,
            "zoo_classes": mc.zoo_classes,
            "ckpt": mc.ckpt_path,
            "outputs": list(self.model.output_names),
            "placement": getattr(mc, "placement", None) or "shard",
            "wire": self.cfg.wire_format,
            "packed_io": bool(self.cfg.packed_io),
            "resize": self.cfg.resize,
            "s2d": bool(getattr(self, "_s2d_handshake", False)),
            "kind": kind,
            "canvas": int(canvas_s),
            "batch": int(bucket),
        }
        if rows is not None:
            key["rows"] = int(rows)
        if extra:
            key.update(extra)
        return key

    def _aot_for(self, rep: _Replica):
        """The AOT cache as far as ``rep`` may use it: None when it is off,
        or when the replica is a sub-mesh of several devices, whose
        executables a TPU cannot load back onto their own devices
        (aotcache.loadable_on) — those compile at every boot, out of
        JAX's persistent cache after the first."""
        if self._aot is None or not aotcache.loadable_on(rep.mesh.devices.flat):
            return None
        return self._aot

    def _get_serve_exe(self, rep: _Replica, canvas_s: int, bucket: int):
        """The AOT-compiled serve executable for one (replica, canvas,
        batch-bucket) shape: per-replica memo → cache deserialize →
        compile (+ write-back). Returns (executable, source) with source
        in {"cached", "deserialized", "compiled"}. Thread-safe: a racing
        duplicate costs one extra compile/deserialize; the memo's
        setdefault keeps one winner."""
        memo_key = ("serve", int(canvas_s), int(bucket))
        exe = rep.exe.get(memo_key)
        if exe is not None:
            return exe, "cached"
        p_avals = jax.tree.map(
            lambda v: jax.ShapeDtypeStruct(v.shape, v.dtype), rep.params
        )
        if self.cfg.packed_io:
            avals = (p_avals, jax.ShapeDtypeStruct(
                self.packed_shape(bucket, canvas_s), jnp.uint8))
        else:
            avals = (
                p_avals,
                jax.ShapeDtypeStruct(
                    self.canvas_shape(bucket, canvas_s), jnp.uint8),
                jax.ShapeDtypeStruct((bucket, 2), jnp.int32),
            )
        key = self._aot_key(rep, "serve", canvas_s, bucket)
        aot = self._aot_for(rep)
        exe = aot.load(key, rep.mesh.devices.flat) if aot is not None else None
        source = "deserialized"
        if exe is None:
            t0 = time.perf_counter()
            exe = rep.serve.lower(*avals).compile()
            aotcache.record_compile_seconds(time.perf_counter() - t0)
            source = "compiled"
            if aot is not None:
                aot.store(key, exe)
        return rep.exe.setdefault(memo_key, exe), source

    def _serve_exe_for(self, rep: _Replica, slab_key0, bucket: int):
        """Dispatch-path lookup: the warmed AOT executable for this
        shape, or the lazy jit wrapper for shapes warmup never saw (the
        correctness fallback — identical program, compiled on use)."""
        exe = rep.exe.get(("serve", canvas_side(slab_key0), bucket))
        return exe if exe is not None else rep.serve

    # ---------------------------------------------------------- parity gate

    # Pinned gate tolerances per serving dtype (probe batch, seeded inputs,
    # all four zoo presets — tests/test_quant.py drives them). ``prob``
    # doubles as the top-k agreement margin; ``topk`` is the minimum
    # agreeing fraction; detect gates sigmoid scores + raw box deltas.
    # Measured worst-case deltas across the zoo (seeded init, probe sizes
    # 64–96px): int8 classify prob ≤0.125 (tiny 64px mobilenet; 0.042 at
    # 96px) with top-k agreement 1.0 throughout — agreement is the primary
    # classify gate, the prob bound a backstop. Detect raw boxes are
    # unbounded regression outputs, so their L∞ bound carries more slack
    # (int8 measured 0.168; sigmoid scores 0.040).
    _PARITY_TOL = {
        "int8": {"prob": 0.15, "topk": 0.90, "score": 0.06, "box": 0.25},
        "bfloat16": {"prob": 0.08, "topk": 0.90, "score": 0.05, "box": 0.15},
    }

    def parity_check(self, batch: int = 4, seed: int = 0) -> dict:
        """Golden numerical-parity gate vs the float32 path.

        Runs this engine's model computation exactly as the serve fn traces
        it (quantized dequant-on-the-fly, fused depthwise, compute dtype)
        against an UNfused float32 reference sharing the identical param
        values, on a seeded probe batch in the model's input layout.
        Classify gates margin-aware top-k agreement + max prob delta;
        detect gates sigmoid-score and raw-box L∞ deltas. Called at engine
        build for quantized dtypes (a failure turns the registry load into
        FAILED); callable on any engine for the bench's A/B rows.
        """
        tol = self._PARITY_TOL.get(self.model_cfg.dtype, self._PARITY_TOL["bfloat16"])
        spec0 = self.model.input_specs[0]
        shape = (batch, *spec0.shape[1:])
        rs = np.random.RandomState(seed)
        x = rs.uniform(-1.0, 1.0, size=shape).astype(np.float32)

        dtype = self._dtype
        policy = None if dtype == jnp.float32 else dtype
        model_fn = self.model.fn
        if self._quantized:
            q_params = quant.quantize_params(self.model.params, dtype)
        else:
            q_params = {
                k: np.asarray(v).astype(dtype)
                if np.asarray(v).dtype == np.float32 else np.asarray(v)
                for k, v in self.model.params.items()
            }

        def q_fn(params, xin):
            if self._quantized:
                params = quant.dequantize_tree(params, dtype)
            outs = model_fn(params, xin.astype(dtype), float_dtype=policy)
            return tuple(o.astype(jnp.float32) for o in outs)

        ref_model_fn = self.model.fn
        if self._fused_dw:
            # The reference must be the STOCK (unfused) forward; rebuild the
            # module only — it consumes the same param dict (identical tree),
            # so the f32 golden params feed both paths.
            from ..models.adapter import native_converted

            ref_model_fn = native_converted(
                self.model_cfg.name,
                num_classes=self.model_cfg.zoo_classes,
                width=self.model_cfg.zoo_width,
                input_size=self.model_cfg.input_size[0],
                input_format="s2d" if self._s2d_handshake else "nhwc",
                fused_dw=False,
            ).fn

        def ref_fn(params, xin):
            outs = ref_model_fn(params, xin, float_dtype=None)
            return tuple(o.astype(jnp.float32) for o in outs)

        q_outs = [np.asarray(o) for o in jax.jit(q_fn)(q_params, x)]
        ref_outs = [np.asarray(o) for o in jax.jit(ref_fn)(self.model.params, x)]

        out = {
            "dtype": self.model_cfg.dtype,
            "fused_dw": self._fused_dw,
            "task": self.model_cfg.task,
            "probe_batch": batch,
        }
        if self.model_cfg.task == "detect":
            by_name_q = dict(zip(self.model.output_names, q_outs))
            by_name_r = dict(zip(self.model.output_names, ref_outs))
            sig = lambda v: 1.0 / (1.0 + np.exp(-v))
            score_d = float(np.max(np.abs(
                sig(by_name_q["raw_scores"]) - sig(by_name_r["raw_scores"]))))
            box_d = float(np.max(np.abs(
                by_name_q["raw_boxes"] - by_name_r["raw_boxes"])))
            out.update(
                max_score_delta=round(score_d, 5), max_box_delta=round(box_d, 5),
                tol_score=tol["score"], tol_box=tol["box"],
                **{"pass": score_d <= tol["score"] and box_d <= tol["box"]},
            )
        else:
            k = min(self.model_cfg.topk, q_outs[0].shape[-1])
            prob_d = float(np.max(np.abs(q_outs[0] - ref_outs[0])))
            agree = quant.topk_agreement(ref_outs[0], q_outs[0], k, tol["prob"])
            out.update(
                max_prob_delta=round(prob_d, 5),
                topk_agreement=round(agree, 4), topk=k,
                tol_prob=tol["prob"], tol_topk=tol["topk"],
                **{"pass": prob_d <= tol["prob"] and agree >= tol["topk"]},
            )
        return out

    # ---------------------------------------------------------------- serve

    def pick_batch_bucket(self, n: int) -> int:
        for b in self.batch_buckets:
            if n <= b:
                return b
        return self.batch_buckets[-1]

    def acquire_staging(self, n: int, row_shape: tuple[int, ...]) -> StagingSlab:
        """A staging slab whose batch bucket fits ``n`` rows of ``row_shape``
        canvases. Pooled slabs are reused; when none is free a new one is
        allocated (pipelined callers may hold many slabs in flight, so
        acquisition must never block). Slabs return to the pool when
        :meth:`fetch_outputs` completes their batch."""
        bucket = self.pick_batch_bucket(n)
        if n > bucket:
            # Never hand jax.jit a never-compiled shape: a batch above the top
            # bucket would pay a request-time compile — the exact stall warmup
            # exists to prevent. Callers split (run_batch does) or re-config.
            raise ValueError(
                f"batch of {n} exceeds the top batch bucket {bucket}; "
                "split the batch or raise batch_buckets/max_batch"
            )
        return self._acquire(
            (tuple(row_shape), bucket),
            lambda: StagingSlab(row_shape, bucket, self.cfg.packed_io))

    def acquire_ragged(self, n: int, canvas_s: int) -> RaggedSlab:
        """A ragged arena slab whose batch bucket fits ``n`` images at
        canvas bucket ``canvas_s``. Same pool and lifecycle as
        :meth:`acquire_staging` — release via :meth:`release_staging` when
        never dispatched, or :meth:`dispatch_ragged` → :meth:`fetch_outputs`
        otherwise."""
        bucket = self.pick_batch_bucket(n)
        if n > bucket:
            raise ValueError(
                f"batch of {n} exceeds the top batch bucket {bucket}; "
                "split the batch or raise batch_buckets/max_batch"
            )
        return self._acquire(
            (("ragged", int(canvas_s)), bucket),
            lambda: RaggedSlab(canvas_s, bucket, paged=self._paged))

    def _acquire(self, key: tuple, make):
        """Take a pooled slab of ``key`` or ``make()`` one, count it as out
        and arm it. A pooled slab is handed out as it came back: its bytes
        are the last batch's (what a batch reads of a slab is bounded by
        its own hws / meta table, so stale bytes are never observable)."""
        slab = None
        with self._staging_lock:
            self._staging_last_use[key] = time.monotonic()
            self._staging_acquires += 1
            free = self._staging_pool.get(key)
            if free:
                slab = free.pop()
                self._staging_pool_nbytes -= slab.total_bytes
                self._staging_out += 1
                self._staging_out_nbytes += slab.total_bytes
            else:
                self._staging_allocs += 1
        if slab is None:
            slab = make()  # outside the lock: maps the arena
            with self._staging_lock:
                self._staging_out += 1
                self._staging_out_nbytes += slab.total_bytes
        # Pool return is the conjunction of fetch-complete AND all slot
        # leases dropped (StagingSlab docstring); the slab itself enforces
        # it so a straggling lessee can never overlap a reused buffer.
        slab.arm(self._release_staging)
        return slab

    def release_staging(self, slab: StagingSlab):
        """Recycle a slab that was acquired but never dispatched (e.g. a
        batch builder sealed with only holes). Routed through the slab's
        lease refcount, so stray lessees still hold it back. Pages it
        shipped early are dropped."""
        if getattr(slab, "paged", False):
            slab.take_pages(0)
        slab.finish_fetch()

    def _release_staging(self, slab: StagingSlab):
        dropped = []  # unmapped after the lock is released
        with self._staging_lock:
            self._staging_out -= 1
            self._staging_out_nbytes -= slab.total_bytes
            self._staging_last_use[slab.key] = time.monotonic()
            free = self._staging_pool.setdefault(slab.key, [])
            # Over the per-key cap the slab is dropped: bounded host memory
            # under bursty pipelining.
            if len(free) < self._staging_cap:
                free.append(slab)
                self._staging_pool_nbytes += slab.total_bytes
            # The idle budget (see __init__): the floor plus what is still
            # out. Over it, drop slabs from the least-recently-used shapes
            # first, so warmup-only buckets give their memory back to the
            # shapes traffic actually hits. This return lowered the budget,
            # so the trim runs whether or not the slab was kept.
            budget = self._staging_budget + self._staging_out_nbytes
            while self._staging_pool_nbytes > budget:
                victim = min(
                    (k for k, v in self._staging_pool.items() if v),
                    key=lambda k: self._staging_last_use.get(k, 0.0),
                    default=None,
                )
                if victim is None:
                    break
                evicted = self._staging_pool[victim].pop()
                self._staging_pool_nbytes -= evicted.total_bytes
                dropped.append(evicted)

    def staging_stats(self) -> dict:
        with self._staging_lock:
            out = {
                "slab_acquires_total": self._staging_acquires,
                "slab_allocs_total": self._staging_allocs,
                "slabs_out": self._staging_out,
                "slabs_out_bytes": self._staging_out_nbytes,
                "slabs_pooled": sum(len(v) for v in self._staging_pool.values()),
                "slabs_pooled_bytes": self._staging_pool_nbytes,
            }
        # Sequentially after the staging lock, never nested: the route
        # lock ranks ABOVE it (outermore, rank 25 vs 50 in lockorder.toml),
        # so acquiring it while still holding the staging lock would be an
        # order violation.
        with self._route_lock:
            reps = [
                {
                    "replica": rep.index,
                    "devices": int(rep.mesh.devices.size),
                    "param_device_ids": rep.param_device_ids,
                    "param_bytes": rep.param_bytes,
                    "dispatches_total": rep.dispatches_total,
                    "dispatches_inflight": rep.dispatches_inflight,
                    "slab_bytes_inflight": rep.slab_bytes_inflight,
                    "busy_s": round(rep.busy_s, 3),
                }
                for rep in self._replicas
            ]
        # Aggregates keep their historical names; the per-replica block is
        # what /stats and /metrics attribute per chip group.
        out["dispatches_total"] = sum(r["dispatches_total"] for r in reps)
        out["dispatches_inflight"] = sum(r["dispatches_inflight"] for r in reps)
        out["placement"] = self.placement.summary()
        out["replicas"] = reps
        return out

    def device_memory(self) -> list[dict]:
        """Per-device memory as the backend reports it (/stats
        "device_memory"): bytes in use, the peak, and the limit. The CPU
        backend reports none, and its rows carry the id alone."""
        keys = ("bytes_in_use", "peak_bytes_in_use", "bytes_limit")
        rows = []
        for d in self.mesh.devices.flat:
            ms = d.memory_stats() or {}
            rows.append({"id": int(d.id),
                         **{k: int(ms[k]) for k in keys if k in ms}})
        return rows

    def econ_stats(self) -> list[dict]:
        """Per-replica device-economics counters for the /stats "economics"
        block (serving/costmodel.economics_snapshot joins them with the
        analytic cost card): one row per (canvas, batch-bucket) cell a
        dispatch has actually exercised."""
        with self._route_lock:
            return [
                {
                    "replica": rep.index,
                    "devices": int(rep.mesh.devices.size),
                    "buckets": [
                        {
                            "canvas": ck, "batch_bucket": bk,
                            "batches": c[0], "rows": c[1],
                            "rows_dispatched": c[2],
                            "device_s": round(c[3], 4),
                            # Ragged wire only (0.0 otherwise): exact used
                            # arena rows before the shipped-prefix
                            # quantization — the same-unit numerator of
                            # the wire-padding fraction.
                            "rows_tight": round(c[4], 3),
                        }
                        for (ck, bk), c in sorted(rep.econ.items())
                    ],
                }
                for rep in self._replicas
            ]

    # -------------------------------------------------------------- routing

    def route_replica(self) -> int:
        """Pick the dispatch replica for one batch: round-robin order with
        a least-loaded override (in-flight dispatch count per replica), so
        equal load walks the replicas cyclically and a slow replica sheds
        work to its idler siblings instead of queueing behind itself."""
        if self.num_replicas == 1:
            return 0
        with self._route_lock:
            loads = [rep.dispatches_inflight for rep in self._replicas]
            start = self._rr
            n = self.num_replicas
            best = min(range(n), key=lambda i: (loads[i], (i - start) % n))
            self._rr = (best + 1) % n
            return best

    def replica_loads(self) -> list[int]:
        """In-flight dispatch count per replica — the batcher's routing
        input (and the least-loaded tiebreak's definition of load)."""
        with self._route_lock:
            return [rep.dispatches_inflight for rep in self._replicas]

    def placement_summary(self) -> dict:
        """JSON-ready placement description for /models and /stats."""
        return self.placement.summary()

    # ------------------------------------------------------------- dispatch

    def dispatch_staged(self, slab: StagingSlab, n: int, spans=(),
                        replica: int | None = None, rec: dict | None = None):
        """Dispatch a filled staging slab (async); returns an opaque handle
        for :meth:`fetch_outputs`. ``replica`` pins the dispatch stream
        (the batcher routes at seal time); None routes here via
        :meth:`route_replica`. ``spans`` (request trace spans) get a
        ``replica`` note, so per-chip attribution survives into the access
        log and flight recorder; their device stages are the batcher's,
        from the stamps of the batch's flight. ``rec`` is the batcher's
        record of this batch (Batcher._hand_off): its ``seq`` and ``rows``
        name the profiler annotations (``twd.h2d``, ``twd.h2d_flight``,
        ``twd.serve_enqueue``, ``twd.d2h_start``), and ``t_put``,
        ``h2d_bytes`` and the flight's stamps (:class:`Flight`) are written
        into it.

        Dispatch and fetch are split so the batcher's pipeline can overlap
        batch N+1's transfer/compute with batch N's execute and device→host
        fetch (JAX dispatch is asynchronous, and this method is safe to
        call from several launch threads at once — each slab belongs to
        exactly one batch, and replicas dispatch fully concurrently). On
        the packed wire this is exactly ONE host→device transfer per batch,
        straight from the reused slab — the explicit device_put carries the
        replica's exact input sharding so the jitted call never sees numpy
        (implicit transfer paths block), and the device→host copy of the
        outputs starts at dispatch time so the fetch side pays neither
        compute wait nor transfer round-trip latency when it finally blocks.
        """
        slab.pad_from(n)
        # The slot-lease batcher acquires top-capacity slabs before it knows
        # the final batch size, so dispatch re-buckets: ship only the prefix
        # covering the compiled bucket for n rows (a contiguous view — still
        # ONE transfer, and it keeps occupancy/wire bytes proportional to
        # the real batch, not the builder's capacity).
        bucket = self.pick_batch_bucket(n)
        r = self.route_replica() if replica is None else int(replica)
        rep = self._replicas[r]
        # Accounted BEFORE the device work so concurrent routers see this
        # dispatch as load while the transfer is still in flight.
        with self._route_lock:
            rep.dispatches_total += 1
            rep.dispatches_inflight += 1
            rep.slab_bytes_inflight += slab.total_bytes
        guard = rep.dispatch_guard if rep.serialize else _NO_LOCK
        try:
            outs, t_put, nbytes, flight = self._dispatch_on(
                rep, guard, slab, bucket, rec)
        except BaseException:
            # Roll the LIVE accounting back: a failed dispatch never
            # reaches fetch_outputs, and leaked in-flight counts would make
            # the router shun this replica forever. dispatches_total stays
            # — it exports as a Prometheus counter, and counters must never
            # decrease (a rollback would read as a counter reset and fake a
            # rate() spike).
            with self._route_lock:
                rep.dispatches_inflight -= 1
                rep.slab_bytes_inflight -= slab.total_bytes
            raise
        if rec is not None:
            rec["t_put"], rec["h2d_bytes"] = t_put, nbytes
        for s in spans:
            s.note("replica", r)
        return outs, (n, slab, r, flight, bucket)

    def ship_pages(self, slab: RaggedSlab, pages, seq: int | None = None):
        """Hand ``pages`` of an open batch's arena (ready by
        :meth:`RaggedSlab.settle`) to the shipper of the replica the arena
        is bound to; the first pages bind it (:meth:`route_replica`), and
        :meth:`dispatch_ragged` launches it there. ``seq`` is the batch's,
        for the annotation. Called under the batcher's condition: it only
        queues work."""
        from ..ops.image import unpack_kernel_applies

        if slab.replica is None:
            slab.replica = self.route_replica()
        rep = self._replicas[slab.replica]
        self._shipper(rep).put(slab, pages, seq,
                               unpack_kernel_applies(slab.canvas_s, 1))

    def _arena_pages(self, nbytes: int) -> list[int]:
        """The sizes an arena prefix of ``nbytes`` ships in, in arena order:
        its pages (:func:`page_sizes`) where arenas ship by pages, else the
        prefix whole."""
        return page_sizes(nbytes) if self._paged else [nbytes]

    def _shipper(self, rep: _Replica) -> PageShipper:
        if rep.shipper is None:
            with self._route_lock:
                if rep.shipper is None:
                    rep.shipper = PageShipper(rep.data_sharding,
                                              f"page-ship-{rep.index}")
        return rep.shipper

    def _flights(self, rep: _Replica) -> FlightLog:
        if rep.flights is None:
            with self._route_lock:
                if rep.flights is None:
                    rep.flights = FlightLog(self._route_lock,
                                            f"h2d-watch-{rep.index}")
        return rep.flights

    def _dispatch_on(self, rep: _Replica, guard, slab: StagingSlab,
                     bucket: int, rec: dict | None):
        """The guarded device work of one dispatch: host→device transfer +
        execute enqueue + async D2H start on ``rep``'s stream, each under
        its profiler annotation (named by ``rec``'s seq and rows). Returns
        (outputs, when the last ``device_put`` returned, bytes shipped, the
        call's :class:`Flight`)."""
        serve = self._serve_exe_for(rep, slab.key[0], bucket)
        label = f"c{canvas_side(slab.key[0])} b{bucket}"
        ids = _batch_ids(rec)
        flights = self._flights(rep)
        with guard:
            if self.cfg.packed_io:
                buf = slab.buf if bucket == slab.bucket else slab.buf[:bucket]
                nbytes = buf.nbytes
                flight = flights.start(rec, label, nbytes)
                with stage(None, "h2d", label, **ids) as put:
                    # twdlint: disable=no-blocking-under-lock(the per-replica dispatch guard EXISTS to hold device enqueue: two concurrent multi-device XLA:CPU dispatches into ONE replica interleave per-device partitions and deadlock the collective rendezvous; disjoint replicas never contend, and the guard is a nullcontext off CPU / on single-device replicas)
                    bufs = (jax.device_put(buf, rep.data_sharding),)
                flights.copying(flight, bufs)
                with stage(None, "serve_enqueue", label, **ids):
                    outs = serve(rep.params, *bufs)
            else:
                trim = bucket != slab.bucket
                canvases = slab.canvases[:bucket] if trim else slab.canvases
                hws = slab.hws[:bucket] if trim else slab.hws
                nbytes = canvases.nbytes + hws.nbytes
                flight = flights.start(rec, label, nbytes)
                with stage(None, "h2d", label, **ids) as put:
                    # twdlint: disable=no-blocking-under-lock(same per-replica XLA:CPU rendezvous serialization as the packed branch — the guarded region is exactly the device enqueue)
                    canvases_d = jax.device_put(canvases, rep.data_sharding)
                    # twdlint: disable=no-blocking-under-lock(same per-replica XLA:CPU rendezvous serialization as the packed branch)
                    hws_d = jax.device_put(hws, rep.data_sharding)
                flights.copying(flight, (canvases_d, hws_d))
                with stage(None, "serve_enqueue", label, **ids):
                    outs = serve(rep.params, canvases_d, hws_d)
            with stage(None, "d2h_start", label, **ids):
                for leaf in jax.tree.leaves(outs):
                    leaf.copy_to_host_async()
        flights.enqueued(flight)
        return outs, put.t1, nbytes, flight

    def _ragged_unpack(self, rep: _Replica, canvas_s: int, bucket: int,
                       rows: int, counts: dict | None = None):
        """The compiled device-side unpack stage for one (replica, canvas
        bucket, batch bucket, shipped-rows) shape: flat byte arena + meta →
        (canvases, hws) exactly as the host-padded wire would have staged
        them, sharded for the replica's serve fn. The program takes the
        prefix as the tuple of its pages (:meth:`_arena_pages`: one, where
        arenas do not ship by pages), in arena order, and joins them on the
        device first. Returns (executable, arena input
        sharding, whether the arena ships as uint32 words for the Mosaic
        kernel — ops.image.unpack_kernel_applies: a TPU, a one-device mesh,
        a canvas of whole lane rows; bytes and the XLA gather otherwise).
        AOT-compiled on first use (deserialize from the executable cache
        when one is configured, else lower+compile, with write-back) —
        compilation happens OUTSIDE the ragged lock, which only memoizes
        the result. Warmup covers every quantized rows variant;
        rows_shipped bounds them at ~8 per (canvas, bucket) pair.
        ``counts`` (warmup's attribution dict) gets "compiled" /
        "deserialized" bumped for a build."""
        key = (rep.index, int(canvas_s), bucket, rows)
        with self._ragged_lock:
            hit = self._ragged_fns.get(key)
        if hit is not None:
            return hit
        from ..ops.image import (RAGGED_UNPACK_VERSION, unpack_kernel_applies,
                                 unpack_ragged)

        # Shard the arena over 'data' only when the byte count divides the
        # submesh; otherwise ship it replicated — the host→device wire is
        # 1x either way (GSPMD gathers on device for the shared-operand
        # gather), and quantized row counts make divisibility the common
        # case.
        nbytes = rows * canvas_s * canvas_s * 3
        ndev = int(rep.mesh.devices.size)
        arena_sh = rep.data_sharding if nbytes % ndev == 0 else rep.replicated
        kernel = unpack_kernel_applies(int(canvas_s), ndev)
        akey = self._aot_key(
            rep, "unpack", canvas_s, bucket, rows=rows,
            extra={"unpack_version": RAGGED_UNPACK_VERSION,
                   "arena_sharded": nbytes % ndev == 0,
                   "arena_words": kernel,
                   "page_bytes": PAGE_BYTES if self._paged else 0},
        )
        aot = self._aot_for(rep)
        exe = (aot.load(akey, rep.mesh.devices.flat)
               if aot is not None else None)
        if exe is not None:
            if counts is not None:
                counts["deserialized"] = counts.get("deserialized", 0) + 1
        else:
            arena = tuple(
                jax.ShapeDtypeStruct((n // 4,), jnp.uint32) if kernel
                else jax.ShapeDtypeStruct((n,), jnp.uint8)
                for n in self._arena_pages(nbytes))
            fn = jax.jit(
                lambda pages, meta: unpack_ragged(
                    _join_pages(pages), meta, int(canvas_s)),
                in_shardings=(tuple(arena_sh for _ in arena), rep.replicated),
                out_shardings=(rep.data_sharding, rep.data_sharding),
            )
            t0 = time.perf_counter()
            exe = fn.lower(
                arena, jax.ShapeDtypeStruct((bucket, 4), jnp.int32),
            ).compile()
            aotcache.record_compile_seconds(time.perf_counter() - t0)
            if counts is not None:
                counts["compiled"] = counts.get("compiled", 0) + 1
            if aot is not None:
                aot.store(akey, exe)
        with self._ragged_lock:
            hit = self._ragged_fns.setdefault(key, (exe, arena_sh, kernel))
        return hit

    def dispatch_ragged(self, slab: RaggedSlab, n: int, spans=(),
                        replica: int | None = None, rec: dict | None = None):
        """Dispatch a filled ragged arena (async) — the tight-wire sibling
        of :meth:`dispatch_staged`. Ships the arena's used prefix (see
        :meth:`RaggedSlab.rows_shipped`; by pages, those not on the device
        yet, where arenas ship by pages) plus the meta table, enqueues the
        jitted device-side unpack (annotation ``twd.unpack_enqueue``), then
        the replica's serve fn; the handle feeds the SAME
        :meth:`fetch_outputs`. An arena bound to a replica by its first
        early page launches there, whatever ``replica`` says. ``spans`` and
        ``rec`` as in :meth:`dispatch_staged`, with ``t_pre`` (the unpack
        enqueued), ``unpack_kernel`` (the unpack ran the Mosaic kernel),
        ``h2d_pages`` (the prefix's pages), ``h2d_pages_early`` and
        ``h2d_early_bytes`` (those whose copy started before the batch's
        ``t_launch``) besides; ``h2d_bytes`` stays the whole prefix plus
        the meta table."""
        bucket = self.pick_batch_bucket(n)
        r = (slab.replica if slab.replica is not None
             else self.route_replica() if replica is None else int(replica))
        rep = self._replicas[r]
        with self._route_lock:
            rep.dispatches_total += 1
            rep.dispatches_inflight += 1
            rep.slab_bytes_inflight += slab.total_bytes
        guard = rep.dispatch_guard if rep.serialize else _NO_LOCK
        try:
            outs, t_put, t_pre, nbytes, kernel, flight, (early, pages) = \
                self._dispatch_ragged_on(rep, guard, slab, bucket, rec)
        except BaseException:
            # Same live-accounting rollback as dispatch_staged; the totals
            # stay (Prometheus counters must never decrease).
            with self._route_lock:
                rep.dispatches_inflight -= 1
                rep.slab_bytes_inflight -= slab.total_bytes
            raise
        if rec is not None:
            rec["t_put"], rec["t_pre"], rec["h2d_bytes"] = t_put, t_pre, nbytes
            rec["unpack_kernel"] = kernel
            rec["h2d_pages"], rec["h2d_pages_early"] = pages, early
            rec["h2d_early_bytes"] = early * PAGE_BYTES
        for s in spans:
            s.note("replica", r)
        return outs, (n, slab, r, flight, bucket)

    def _dispatch_ragged_on(self, rep: _Replica, guard, slab: RaggedSlab,
                            bucket: int, rec: dict | None):
        """Guarded device work of one ragged dispatch: ship arena prefix
        (the pages not on the device yet, where it ships by pages) + meta,
        enqueue unpack, enqueue serve, start the async D2H copy, each under
        its profiler annotation. Returns (outputs, when the second
        ``device_put`` returned, when the unpack was enqueued, bytes
        shipped, whether the unpack is the kernel, the call's
        :class:`Flight`, (pages whose copy started before ``t_launch``,
        the prefix's pages; (0, 0) unpaged))."""
        rows = slab.rows_shipped(bucket)
        unpack, arena_sh, kernel = self._ragged_unpack(
            rep, slab.canvas_s, bucket, rows)
        serve = self._serve_exe_for(rep, slab.key[0], bucket)
        prefix = rows * slab.row_bytes
        sizes = self._arena_pages(prefix)
        # Only whole pages of the prefix can have gone early.
        early = slab.take_pages(prefix // PAGE_BYTES) if slab.paged else {}
        bufs = [slab.buf[p * PAGE_BYTES:p * PAGE_BYTES + n]
                for p, n in enumerate(sizes) if p not in early]
        if kernel:
            bufs = [b.view(np.uint32) for b in bufs]  # the same bytes on the wire
        t_launch = (rec or {}).get("t_launch") or time.monotonic()
        counted = (sum(t < t_launch for _, t in early.values()),
                   len(sizes) if slab.paged else 0)
        meta = slab.meta if bucket == slab.bucket else slab.meta[:bucket]
        label = f"c{slab.canvas_s} b{bucket}"
        ids = _batch_ids(rec)
        flights = self._flights(rep)
        nbytes = prefix + meta.nbytes
        with guard:
            flight = flights.start(rec, label, nbytes)
            with stage(None, "h2d", label, **ids) as put:
                # twdlint: disable=no-blocking-under-lock(same per-replica XLA:CPU rendezvous serialization as _dispatch_on — the guarded region is exactly the device enqueue)
                put_d = jax.device_put(bufs, arena_sh)
                # twdlint: disable=no-blocking-under-lock(same per-replica XLA:CPU rendezvous serialization as _dispatch_on)
                meta_d = jax.device_put(meta, rep.replicated)
            tail = iter(put_d)
            arena_d = tuple(early[p][0] if p in early else next(tail)
                            for p in range(len(sizes)))
            flights.copying(flight, (*arena_d, meta_d))
            with stage(None, "unpack_enqueue", label, **ids) as pre:
                canvases_d, hws_d = unpack(arena_d, meta_d)
            with stage(None, "serve_enqueue", label, **ids):
                outs = serve(rep.params, canvases_d, hws_d)
            with stage(None, "d2h_start", label, **ids):
                for leaf in jax.tree.leaves(outs):
                    leaf.copy_to_host_async()
        flights.enqueued(flight)
        return outs, put.t1, pre.t1, nbytes, kernel, flight, counted

    def dispatch_batch(self, canvases: np.ndarray, hws: np.ndarray,
                       replica: int | None = None):
        """Compat path for already-stacked batches (run_batch, warmup,
        bench): one vectorized copy into a pooled slab, then the same
        single-transfer dispatch the batcher's row-staged path uses."""
        slab = self.acquire_staging(canvases.shape[0], tuple(canvases.shape[1:]))
        slab.write_rows(canvases, hws)
        return self.dispatch_staged(slab, canvases.shape[0], replica=replica)

    def fetch_outputs(self, handle, rec: dict | None = None
                      ) -> tuple[np.ndarray, ...]:
        """Block on a dispatched batch and return numpy outputs sliced to the
        real batch size (packed path: split the single fetched array back
        into per-output views using the traced tail shapes). Completing the
        fetch proves the device consumed the inputs, so the batch's staging
        slab becomes pool-eligible here — actual return waits for any
        straggling slot lessee via the slab's refcount. The wait for the
        outputs to be computed (which stamps the flight's ``t_ready``), then
        the blocking conversion, lie under the annotation ``twd.fetch``,
        named by ``rec`` (the batcher's record of the batch), which also
        receives ``d2h_bytes``."""
        outs, (n, slab, r, flight, bucket) = handle
        rep = self._replicas[r]
        wait = stage(None, "fetch", f"c{canvas_side(slab.key[0])} b{bucket}",
                     **_batch_ids(rec))
        try:
            with wait:
                self._flights(rep).land(flight, outs)
                host = (np.asarray(outs) if self.cfg.packed_io
                        else jax.tree.map(np.asarray, outs))
            nbytes = sum(o.nbytes for o in jax.tree.leaves(host))
            self.note_d2h(nbytes)
            if rec is not None:
                rec["d2h_bytes"] = nbytes
            if self.cfg.packed_io:
                # The conversion transfers the FULL compiled bucket (the
                # device array is one buffer); the slice to n happens on
                # host — which is exactly why the DAG executor's partial
                # row fetches beat this path on D2H bytes/image.
                packed = host[:n]
                result = []
                off = 0
                for shape, dt in self._out_tails:
                    size = int(np.prod(shape, dtype=np.int64)) if shape else 1
                    chunk = packed[:, off : off + size].reshape(n, *shape)
                    # int outputs (top-k indices, class ids, counts) ride as
                    # f32 in the packed array — exact for every value they
                    # can take.
                    result.append(chunk.astype(dt) if dt != np.float32 else chunk)
                    off += size
                return tuple(result)
            if self.counter_names:
                # The model's last output is the call's counters, not a
                # row a request: the batcher sums them into /stats.
                *host, counted = host
                host = tuple(host)
                if rec is not None:
                    rec["model_counters"] = dict(
                        zip(self.counter_names, (float(v) for v in counted)))
            host = jax.tree.map(lambda o: o[:n], host)
            return host if isinstance(host, tuple) else (host,)
        finally:
            self._close_flight(rep, n, slab, bucket, flight)

    def _close_flight(self, rep: _Replica, n: int, slab, bucket: int,
                      flight: Flight):
        """A dispatched batch is done with (fetched, or released by the DAG
        path): its replica's in-flight accounting, the device phase into
        ``busy_s`` and the economics cell, and the slab's pool-return."""
        ekey = (canvas_side(slab.key[0]), bucket)
        with self._route_lock:
            rep.dispatches_inflight -= 1
            rep.slab_bytes_inflight -= slab.total_bytes
            rep.busy_s += flight.device_s
            # Economics cell for this (canvas, batch-bucket): batches, rows
            # staged, rows the compiled shape dispatched, device seconds —
            # the measured inputs of the roofline gauges.
            cell = rep.econ.get(ekey)
            if cell is None:
                cell = rep.econ[ekey] = [0, 0, 0, 0.0, 0.0]
            cell[0] += 1
            cell[1] += n
            # Ragged batches ship quantized arena rows, not the full
            # bucket — the whole point of the wire; the economics padding
            # gauges must see what actually crossed it. The tight-rows term
            # (exact used bytes, before the shipped-prefix quantization) is
            # the same-unit numerator the wire-padding fraction needs:
            # requests (cell[1]) count images, which on this wire occupy
            # FEWER rows than they number, so rows/rows_dispatched would go
            # negative.
            if getattr(slab, "is_ragged", False):
                cell[2] += slab.rows_shipped(bucket)
                cell[4] += slab.used / slab.row_bytes
            else:
                # Full-canvas dispatch: every real image occupies exactly
                # one canvas row, so the payload IS n tight rows. Without
                # this, warmup/healthcheck batches (and any classic-path
                # dispatch on a ragged engine) would read as pure padding
                # in the ragged aggregate.
                cell[2] += bucket
                cell[4] += n
            cell[3] += flight.device_s
        slab.finish_fetch()

    # ------------------------------------------------- DAG (device-resident)

    def note_d2h(self, nbytes: int) -> None:
        """Account device→host traffic (bytes). fetch_outputs calls this
        for its full-buffer conversions; the DAG executor calls it for
        the partial row slices it converts itself."""
        with self._route_lock:
            self._d2h_bytes += int(nbytes)

    @property
    def d2h_bytes_total(self) -> int:
        with self._route_lock:
            return self._d2h_bytes

    def device_outputs(self, handle) -> tuple:
        """Structured DEVICE views of a dispatched batch's outputs — no
        device→host transfer. On the packed wire the single packed array
        splits back into per-output device arrays via on-device slicing
        (the same tail walk fetch_outputs does on host). The caller still
        owes the handle a :meth:`fetch_outputs` or
        :meth:`release_dispatch` — this only *reads* the device arrays."""
        outs = handle[0]
        if not self.cfg.packed_io:
            return outs if isinstance(outs, tuple) else (outs,)
        result = []
        off = 0
        for shape, dt in self._out_tails:
            size = int(np.prod(shape, dtype=np.int64)) if shape else 1
            chunk = outs[:, off : off + size].reshape(outs.shape[0], *shape)
            result.append(chunk.astype(dt) if dt != np.float32 else chunk)
            off += size
        return tuple(result)

    def release_dispatch(self, handle) -> None:
        """Close a dispatched batch's accounting WITHOUT the full D2H
        fetch — the DAG path, where the caller converted only the row
        slices it needed (via :meth:`device_outputs` + its own
        ``np.asarray``, accounted through :meth:`note_d2h`) and the bulky
        padded outputs never cross to the host. Waits for the outputs to be
        computed (the flight's device phase is what ``busy_s`` counts),
        then closes it as :meth:`fetch_outputs` does."""
        outs, (n, slab, r, flight, bucket) = handle
        rep = self._replicas[r]
        try:
            self._flights(rep).land(flight, outs)
        finally:
            self._close_flight(rep, n, slab, bucket, flight)

    def dispatch_device(self, canvases, hws: np.ndarray,
                        replica: int | None = None, spans=()):
        """Dispatch an already-DEVICE-RESIDENT canvas batch (the DAG glue
        path: crops built on device from the upstream stage's boxes) —
        no host staging slab, no host copy of the rows. ``canvases`` is a
        jax array ``[n, S, S, 3]`` uint8; ``hws`` is the small host-side
        ``[n, 2]`` int32 table. Rows pad on device to the compiled batch
        bucket (hw=1×1 holes, the classic padding contract). Returns the
        same handle shape as :meth:`dispatch_staged`, so
        :meth:`fetch_outputs` / :meth:`device_outputs` /
        :meth:`release_dispatch` all compose — a 3-stage DAG chains this
        method off its own device_outputs."""
        n = int(canvases.shape[0])
        row_shape = tuple(int(d) for d in canvases.shape[1:])
        bucket = self.pick_batch_bucket(n)
        hws = np.asarray(hws, np.int32)
        if bucket != n:
            pad = bucket - n
            canvases = jnp.concatenate(
                [canvases, jnp.zeros((pad, *row_shape), jnp.uint8)], axis=0)
            hws = np.concatenate([hws, np.ones((pad, 2), np.int32)], axis=0)
        if self.cfg.packed_io:
            # Rebuild the packed wire row ON DEVICE: canvas bytes + the
            # 4-byte big-endian (h, w) trailer StagingSlab.write_hw lays
            # down — the serve executable sees one identical buffer.
            trailer = hws.astype(">u2").view(np.uint8).reshape(bucket, 4)
            batch = jnp.concatenate(
                [canvases.reshape(bucket, -1), jnp.asarray(trailer)], axis=1)
        else:
            batch = canvases
        slab = _DeviceBatch(row_shape, bucket, int(batch.nbytes)
                            + (0 if self.cfg.packed_io else hws.nbytes))
        r = self.route_replica() if replica is None else int(replica)
        rep = self._replicas[r]
        guard = rep.dispatch_guard if rep.serialize else _NO_LOCK
        serve = self._serve_exe_for(rep, row_shape, bucket)
        with self._route_lock:
            rep.dispatches_total += 1
            rep.dispatches_inflight += 1
            rep.slab_bytes_inflight += slab.total_bytes
        flights = self._flights(rep)
        try:
            with guard:
                flight = flights.start(None, f"c{canvas_side(row_shape)} b{bucket}",
                                       slab.total_bytes)
                # twdlint: disable=no-blocking-under-lock(same per-replica XLA:CPU rendezvous serialization as _dispatch_on — the guarded region is exactly the device enqueue; device_put here is a device-to-device reshard of the already-resident glue output)
                bufs = [jax.device_put(batch, rep.data_sharding)]
                if not self.cfg.packed_io:
                    # twdlint: disable=no-blocking-under-lock(same per-replica XLA:CPU rendezvous serialization as _dispatch_on)
                    bufs.append(jax.device_put(hws, rep.data_sharding))
                flights.copying(flight, bufs)
                outs = serve(rep.params, *bufs)
                for leaf in jax.tree.leaves(outs):
                    leaf.copy_to_host_async()
        except BaseException:
            with self._route_lock:
                rep.dispatches_inflight -= 1
                rep.slab_bytes_inflight -= slab.total_bytes
            raise
        flights.enqueued(flight)
        for s in spans:
            s.note("replica", r)
        return outs, (n, slab, r, flight, bucket)

    def run_batch(self, canvases: np.ndarray, hws: np.ndarray,
                  replica: int | None = None) -> tuple[np.ndarray, ...]:
        """Dispatch + fetch in one call (tests, healthz, simple callers).

        Oversized batches are split into top-bucket chunks (pipelined:
        all chunks dispatch before the first fetch) so callers that never
        configured buckets still get compiled-shape execution. Chunks of a
        split batch route independently — on replicated placement they
        spread across the chips.
        """
        top = self.max_rows(canvases.shape[2])
        n = canvases.shape[0]
        if n <= top:
            return self.fetch_outputs(
                self.dispatch_batch(canvases, hws, replica=replica)
            )
        handles = [
            self.dispatch_batch(canvases[i : i + top], hws[i : i + top],
                                replica=replica)
            for i in range(0, n, top)
        ]
        chunks = [self.fetch_outputs(h) for h in handles]
        return tuple(np.concatenate(parts) for parts in zip(*chunks))

    def _warm_executables(self, rep: _Replica, s: int, b: int) -> dict:
        """Obtain every executable one (replica, canvas, batch) pair
        needs — the serve fn plus, on the ragged wire, every quantized
        shipped-rows unpack variant — deserializing from the AOT cache
        when possible, compiling (+ writing back) otherwise. Pure
        compile/deserialize work: holds no locks, touches no device."""
        counts = {"compiled": 0, "deserialized": 0}
        _, source = self._get_serve_exe(rep, s, b)
        if source in counts:
            counts[source] += 1
        if self.ragged:
            # The unpack stage compiles per shipped-rows shape — warm
            # EVERY quantized variant on every replica (the rows
            # quantization bounds them at ~8 per pair). Tight mixed-size
            # traffic walks several variants per second, and a lazy
            # compile stall inside a measurement window reads as a
            # throughput regression the steady state doesn't have.
            q = max(1, b // 8)
            for rows in range(q, b + 1, q):
                self._ragged_unpack(rep, s, b, rows, counts=counts)
        return counts

    def _warm_execute(self, rep: _Replica, s: int, b: int):
        """Run one real batch (and, on the ragged wire, every unpack
        variant) through the full dispatch/fetch path on ``rep`` — the
        executables already exist, so this is pure execution: device
        buffers allocate, the output D2H path exercises, econ cells
        materialize. Safe to run concurrently across replicas: dispatch
        takes the per-replica guard exactly like request traffic."""
        canvases = np.zeros(self.canvas_shape(b, s), np.uint8)
        hws = np.full((b, 2), s, np.int32)
        self.run_batch(canvases, hws, replica=rep.index)
        if self.ragged:
            meta0 = np.zeros((b, 4), np.int32)
            meta0[:, 1:3] = 1
            guard = rep.dispatch_guard if rep.serialize else _NO_LOCK
            q = max(1, b // 8)
            zeros: dict[int, jax.Array] = {}  # one device page a size
            for rows in range(q, b + 1, q):
                unpack, arena_sh, kernel = self._ragged_unpack(rep, s, b, rows)
                sizes = self._arena_pages(rows * s * s * 3)
                if not self._paged:
                    zeros.clear()  # a whole arena a variant: one at a time
                # Same XLA:CPU collective-rendezvous discipline as the
                # request path: the unpack is a multi-device dispatch, and
                # warmup now executes on several pool threads at once.
                with guard:
                    for n in sizes:
                        if n not in zeros:
                            zero = np.zeros(n, np.uint8)
                            # twdlint: disable=no-blocking-under-lock(same per-replica XLA:CPU rendezvous serialization as _dispatch_on — concurrent warmup threads must not interleave multi-device dispatches into one replica)
                            zeros[n] = jax.device_put(
                                zero.view(np.uint32) if kernel else zero, arena_sh)
                    arena_d = tuple(zeros[n] for n in sizes)
                    # twdlint: disable=no-blocking-under-lock(same per-replica XLA:CPU rendezvous serialization as _dispatch_on)
                    meta_d = jax.device_put(meta0, rep.replicated)
                    out = unpack(arena_d, meta_d)
                    for leaf in jax.tree.leaves(out):
                        # twdlint: disable=no-blocking-under-lock(the unpack's completion wait is part of the guarded XLA:CPU dispatch — releasing the guard mid-execution would readmit the rendezvous interleaving)
                        leaf.block_until_ready()

    def warmup(self, canvas_buckets=None, batch_buckets=None):
        """Ready every (canvas, batch) shape pair before serving traffic,
        on EVERY replica: each replica owns its own executables, and a
        replica the router has simply not picked yet must not pay a
        compile stall on its first real batch.

        Three separately-timed phases (boot-time regressions must be
        attributable — ISSUE 18):

        1. the device→host fetch path's first use, logged on its own
           line (it used to hide inside whichever pair's log line ran
           first; the other one-time cost, the econ peak calibration, is
           logged at engine build);
        2. executables — deserialize-from-AOT-cache or compile, fanned
           out over a bounded thread pool (XLA compiles release the GIL,
           so the fan-out overlaps real compile work) instead of the
           historical serial nested loop;
        3. execution — one real batch per (pair, replica) through the
           full dispatch/fetch path, concurrent across replicas.
        """
        canvas_buckets = canvas_buckets or self.cfg.canvas_buckets
        batch_buckets = batch_buckets or self.batch_buckets
        pairs = [(s, b) for s in canvas_buckets for b in batch_buckets
                 if b <= self.max_rows(s)]
        tasks = [(rep, s, b) for (s, b) in pairs for rep in self._replicas]
        workers = max(1, min(8, len(tasks), os.cpu_count() or 4))
        agg: dict[tuple[int, int], dict] = {
            p: {"compiled": 0, "deserialized": 0, "s": 0.0} for p in pairs
        }

        def prep(task):
            rep, s, b = task
            t = time.perf_counter()
            counts = self._warm_executables(rep, s, b)
            return s, b, counts, time.perf_counter() - t

        with ThreadPoolExecutor(
            max_workers=workers, thread_name_prefix="warmup"
        ) as pool:
            for s, b, counts, dt in pool.map(prep, tasks):
                cell = agg[(s, b)]
                cell["compiled"] += counts["compiled"]
                cell["deserialized"] += counts["deserialized"]
                # Max task time, not sum: the pool overlaps replicas, and
                # the pair's log should read as its wall contribution.
                cell["s"] = max(cell["s"], dt)
        for (s, b) in pairs:
            cell = agg[(s, b)]
            log.info(
                "warmup canvas=%d batch=%d: executables %.2fs "
                "(%d compiled, %d deserialized, x%d replicas)",
                s, b, cell["s"], cell["compiled"], cell["deserialized"],
                self.num_replicas,
            )

        self.max_calls_in_flight = self._calls_that_fit()
        if self.max_calls_in_flight is not None:
            workers = min(workers, self.max_calls_in_flight)

        # Which kernels the serve program really holds: a Mosaic kernel is a
        # tpu_custom_call in the compiled text (0 on the CPU backend, where
        # Pallas runs interpreted). chip_smoke.py reads this line.
        s0, b0 = pairs[0]
        exe0, _ = self._get_serve_exe(self._replicas[0], s0, b0)
        log.info(
            "warmup %s: serve executable canvas=%d batch=%d holds %d "
            "tpu_custom_call(s)", self.model_cfg.serve_name, s0, b0,
            exe0.as_text().count('custom_call_target="tpu_custom_call"'),
        )

        # One-time fetch-path first use: the device→host output path has
        # its own lazy setup cost that used to land in the first pair's
        # timing. One real batch on replica 0 absorbs and attributes it;
        # the execution pass below then measures pure steady-state work.
        s0, b0 = canvas_buckets[0], batch_buckets[0]
        t0 = time.perf_counter()
        self.run_batch(
            np.zeros(self.canvas_shape(b0, s0), np.uint8),
            np.full((b0, 2), s0, np.int32),
            replica=0,
        )
        log.info("warmup: first-use fetch path %.2fs (one-time)",
                 time.perf_counter() - t0)

        t0 = time.perf_counter()
        with ThreadPoolExecutor(
            max_workers=workers, thread_name_prefix="warmexec"
        ) as pool:
            list(pool.map(lambda t: self._warm_execute(*t), tasks))
        log.info("warmup: execution pass %.2fs (%d batches x%d replicas)",
                 time.perf_counter() - t0, len(pairs), self.num_replicas)

    def _calls_that_fit(self) -> int | None:
        """Calls in flight that a device's memory holds beside what is on it
        now (the weights, the executables): what is free, by the backend's
        own count, over the largest temporary of a warmed serve executable,
        by the compiler's. Each call launched and not yet done holds its
        temporaries; the per-bucket pipeline depth knows nothing of their
        size (a token decoder's are gigabytes: one call fits beside its
        weights, where a conv net's fit by the dozen). None where the
        backend reports no memory (the CPU) or the executable no analysis."""
        temp = 0
        for rep in self._replicas:
            for exe in list(rep.exe.values()):
                try:
                    temp = max(temp, int(exe.memory_analysis().temp_size_in_bytes))
                except Exception:
                    return None
        free = [ms["bytes_limit"] - ms["bytes_in_use"] for ms in self.device_memory()
                if "bytes_limit" in ms and "bytes_in_use" in ms]
        if not temp or not free:
            return None
        fit = max(1, min(free) // temp)
        log.info("warmup: %d call(s) in flight fit (largest temporary %.2f GB, %.2f GB free)",
                 fit, temp / 1e9, min(free) / 1e9)
        return fit

    def healthcheck(self) -> bool:
        """One-image device round-trip (SURVEY.md §5.3 /healthz contract)."""
        s = self.cfg.canvas_buckets[0]
        out = self.run_batch(
            np.zeros(self.canvas_shape(1, s), np.uint8), np.full((1, 2), s, np.int32)
        )
        return all(np.all(np.isfinite(o)) for o in out if np.issubdtype(o.dtype, np.floating))

    def close(self):
        """Release this engine's buffers (model-registry unload path): the
        pooled host staging slabs and the strong refs to the replicated
        device params and compiled executables. The engine must not be used
        afterwards — a dispatch would fail on the dropped params, which is
        the correct loud failure for a use-after-unload bug."""
        with self._staging_lock:
            self._staging_pool.clear()
            self._staging_pool_nbytes = 0
            self._staging_last_use.clear()
        with self._ragged_lock:
            self._ragged_fns.clear()
        # Every replica's device-resident copy goes: a drained version must
        # hand back its whole placement's HBM, not just replica 0's.
        for rep in self._replicas:
            rep.params = None
            rep.serve = None
            rep.exe.clear()
            if rep.flights is not None:
                rep.flights.close()
            if rep.shipper is not None:
                rep.shipper.close()
        self._params = None
        self._serve = None
        self._serve_raw = None
        self.model = None

    def prepare(self, image: np.ndarray) -> tuple[np.ndarray, tuple[int, int]]:
        """Host-side staging for one decoded image (canvas + valid size).

        With wire_format="yuv420" the canvas is packed to I420 here, so the
        batcher stacks and ships 1.5 B/px instead of 3.
        """
        canvas, hw = pad_to_canvas(image, self.cfg.canvas_buckets)
        if self.cfg.wire_format == "yuv420":
            canvas = rgb_to_yuv420_canvas(canvas)
        return canvas, hw

    def prepare_bytes(
        self, data: bytes
    ) -> tuple[np.ndarray, tuple[int, int], tuple[int, int]]:
        """Image bytes → (canvas, valid (h, w), original (h, w)).

        The native libjpeg extension decodes JPEGs straight into the wire
        format (with DCT-domain downscale for oversized uploads); other
        formats go through PIL + the numpy packer. Raises if the bytes are
        not a decodable image.
        """
        from ..native import decode_to_canvas

        return decode_to_canvas(data, self.cfg.canvas_buckets, self.cfg.wire_format)
