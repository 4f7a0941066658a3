"""Bulk offline inference jobs: checkpointed manifests through the
serving substrate as a strictly lower-priority traffic class (ISSUE 10,
ROADMAP item 5(b)).

The interactive path serves one HTTP round trip per request; the
batch-256 ~30%-MFU throughput operating point had no serving-path
consumer, so re-indexing a corpus or backfilling predictions meant
driving thousands of images through the latency-tuned path one request
at a time. FlexServe (arxiv 2003.01538) motivates exposing multiple
serving modalities behind one endpoint fleet; "Optimizing Prediction
Serving on Low-Latency Serverless Dataflow" (PAPERS.md) frames the hard
constraint this module is built around: background dataflow must not
steal latency budget from the interactive path.

- **Jobs are manifests, not requests.** ``POST /jobs`` registers a
  manifest of images — multipart uploads spooled under ``--jobs-dir``,
  or a server-side directory glob — and answers 202 immediately. A
  single background runner thread drives manifests through the SAME
  registry/batcher/slab substrate interactive traffic uses, staged as
  the batcher's **bulk traffic class**: builders that assemble up to the
  throughput-mode batch size (``--jobs-batch``, default 256) and only
  take device time when the interactive pipeline has idle depth
  (serving/batcher.py's bulk gate), bounded to ``--jobs-max-inflight``
  bulk batches at once — so interactive p99 stays within one bulk batch
  of its idle value while a job runs.

- **Checkpointed progress.** Results spool to ``results.jsonl`` in
  completed-chunk order (one JSON line per image, manifest order within
  the job); after each chunk the line/byte counts and completion state
  persist to ``checkpoint.json`` (append + fsync BEFORE the checkpoint
  update, so a crash between the two leaves only over-appended lines,
  which recovery truncates). A server restart re-registers every job in
  ``--jobs-dir``; non-terminal jobs resume from their checkpoint with
  zero lost and zero duplicated images — the chunk is the atom of
  progress. Graceful shutdown (SIGTERM → shutdown_gracefully) stops the
  runner at a chunk boundary first, so an in-flight job is never
  silently lost.

- **Incremental result streaming.** ``GET /jobs/{id}/results?offset=N``
  returns the JSON lines from ``N`` on (``X-Job-Next-Offset`` carries
  the resume cursor, ``X-Job-State`` the live lifecycle state); a
  ``wait_s`` long-poll blocks until more results land or the job ends.
  Clients stream a running job by re-polling with the returned offset —
  resumable across client restarts, servable across server restarts.

- **Lifecycle** (mirrors the registry's explicit state machine)::

      QUEUED ──▶ RUNNING ──▶ DONE
                   │  ▲  └──▶ FAILED / CANCELLED
                   ▼  │
                  PAUSED ───▶ CANCELLED

  A hot-swap does not fail a job: the registry's retire listener (fired
  under ``registry.cond`` at the DRAINING flip — the declared
  registry.cond → jobs.cond lock-order edge) PAUSES running jobs on the
  retiring model, and the runner re-resolves the model at its next
  chunk, re-versioning the remaining work onto the new SERVING version
  (both versions are recorded in the job's ``versions`` list). Items in
  flight during the drain retry against the new version — zero lost,
  zero duplicated.

- **Staging and cache interplay.** An item becomes a batch slot through
  the same function as an upload (serving/staging.py ``stage_image``,
  here with ``bulk=True``), so it consults the content-addressed
  response cache (serving/respcache.py) under the same key: the file's
  bytes with the bucket set and the wire, looked up before any lease or
  decode. Bulk re-runs dedup for free, a repeated file costs a hash and
  no decode (a file of the same pixels in other bytes is another entry),
  and a job's misses POPULATE the cache, pre-warming the interactive
  tier for the corpus it just processed. Bulk lookups are accounted
  separately (``bulk`` counters in the cache stats) so the hit-rate the
  interactive dashboard shows is not diluted by batch traffic.

Concurrency: one condition (``jobs.cond``, declared in
tools/twdlint/lockorder.toml between registry.cond and batcher.cond)
guards job state, counters, and the queue. Everything blocking — file
IO, decode, cache waits, batcher futures, registry acquire/release —
runs OUTSIDE it; the registry's listeners only flip flags under it.
"""

from __future__ import annotations

import json
import logging
import os
import re
import threading
import time
from collections import deque
from concurrent.futures import ThreadPoolExecutor
from pathlib import Path

import numpy as np

from ..utils.labels import topk_labels
from ..utils.locks import named_condition
from ..utils.tracing import Span
from .batcher import ShuttingDown as ShuttingDownError
from .registry import ModelNotServing, UnknownModel
from .staging import UndecodableImage, abort_slots, stage_image

log = logging.getLogger("tpu_serve.jobs")

# Lifecycle states: strings (not an Enum) so they serialize into /jobs,
# /metrics labels, and checkpoint files without translation.
QUEUED = "QUEUED"
RUNNING = "RUNNING"
PAUSED = "PAUSED"
DONE = "DONE"
FAILED = "FAILED"
CANCELLED = "CANCELLED"
TERMINAL = (DONE, FAILED, CANCELLED)

# Legal transitions, enforced at every state move: a bug that resumes a
# CANCELLED job or finishes one twice must crash the runner's job loudly,
# never corrupt the checkpoint silently.
_TRANSITIONS = {
    QUEUED: (RUNNING, CANCELLED, FAILED),
    RUNNING: (PAUSED, DONE, FAILED, CANCELLED),
    PAUSED: (RUNNING, CANCELLED, FAILED),
    DONE: (),
    FAILED: (),
    CANCELLED: (),
}

_SAFE_NAME = re.compile(r"[^A-Za-z0-9._-]+")
_IMAGE_SUFFIXES = (".jpg", ".jpeg", ".png", ".bmp", ".gif", ".webp")


class UnknownJob(KeyError):
    """No job registered under that id — the HTTP layer maps this to 404."""


# ------------------------------------------------------------- formatting
# One image's batcher output row → its JSON payload. Shared by the
# single-request path (http.App) and the bulk job runner, and placed HERE
# (not http.py) so jobs.py never imports the HTTP surface.


def clamp_topk(topk: int | None, model_cfg) -> int:
    """THE topk clamp (None = model default; both bounds enforced — a
    negative topk would slice labels from the wrong end). Shared by the
    interactive path (http._predict_on) and every bulk staging/format/
    retry site: the clamped value is part of the cache key, so one
    definition is what keeps the interactive and bulk cache key spaces
    identical."""
    if topk is None:
        return model_cfg.topk
    return min(max(topk, 0), model_cfg.topk)


def format_result_row(row, orig_hw, topk: int, mv, trace_id=None) -> dict:
    """Task-dependent payload for one image (the task and label map belong
    to the resolved model version). ``trace_id`` stamps the trace that
    COMPUTED this payload into the row — the join key that links a bulk
    job's result line back to its chunk span in ``/debug/trace`` and the
    access log (a payload later served from the cache keeps the producing
    trace, which is exactly the one that did the device work)."""
    labels = mv.labels
    if mv.model_cfg.task == "detect":
        out = format_detections(row, orig_hw, labels)
    elif mv.model_cfg.task == "classify":
        # Row is on-device top-k: (scores [K], indices [K]).
        scores, idx = (np.asarray(r) for r in row)
        out = {
            "predictions": [
                {
                    "label": labels[i] if i < len(labels) else f"class_{i}",
                    "index": int(i),
                    "score": float(s),
                }
                for s, i in zip(scores[:topk], idx[:topk])
            ]
        }
    elif mv.model_cfg.task == "generate":
        # Row is one top-k list a step: (scores [steps, K], ids [steps, K]);
        # step s + 1 followed the id that step s put first.
        scores, idx = (np.asarray(r) for r in row)
        out = {
            "steps": [
                [{"label": labels[i] if i < len(labels) else f"token_{i}",
                  "index": int(i), "score": float(s)}
                 for s, i in zip(step_scores[:topk], step_idx[:topk])]
                for step_scores, step_idx in zip(scores, idx)
            ]
        }
    else:
        # raw passthrough task
        probs = np.asarray(row[0]).reshape(-1)
        out = {"predictions": topk_labels(probs, labels, topk)}
    if trace_id is not None:
        out["trace_id"] = trace_id
    return out


def format_detections(row, image_hw, labels) -> dict:
    boxes, scores, classes, num = (np.asarray(r) for r in row)
    n = int(num)
    h, w = image_hw
    dets = []
    for i in range(n):
        y0, x0, y1, x1 = (float(v) for v in boxes[i])
        cls = int(classes[i])
        dets.append(
            {
                "box": [y0 * h, x0 * w, y1 * h, x1 * w],
                "class": cls,
                "label": labels[cls] if cls < len(labels) else f"class_{cls}",
                "score": float(scores[i]),
            }
        )
    return {"detections": dets, "num_detections": n}


# -------------------------------------------------------------------- job


class Job:
    """One bulk manifest and its live progress. State mutations go through
    the owning manager (one condition guards every job); the ``history``
    list records transitions with manager-relative timestamps — the
    lifecycle tests read it, like the registry's version history."""

    __slots__ = ("id", "seq", "dir", "model", "topk", "items", "total",
                 "state", "error", "completed", "cached", "errors",
                 "result_lines", "result_bytes", "chunks_done", "versions",
                 "history", "cancel", "resumed", "created_at", "started_at",
                 "finished_at", "source", "line_index", "tenant", "weight")

    def __init__(self, job_id: str, seq: int, job_dir: Path, model: str,
                 topk: int | None, items: list[dict], source: str,
                 t_rel: float, tenant: str = "default",
                 weight: float = 1.0):
        self.id = job_id
        self.seq = seq
        self.dir = job_dir
        self.model = model
        self.topk = topk
        self.items = items  # [{"name": display, "path": abs path}] in order
        self.total = len(items)
        self.state = QUEUED
        self.error: str | None = None
        self.completed = 0      # images spooled (checkpoint-durable)
        self.cached = 0         # served from / coalesced onto the cache
        self.errors = 0         # per-image error lines (job still finishes)
        self.result_lines = 0
        self.result_bytes = 0
        # Byte offset where each checkpoint-covered result line starts —
        # appended with result_lines under the manager's condition, so a
        # streaming poll is one seek instead of a whole-file line scan.
        self.line_index: list[int] = []
        self.chunks_done = 0
        self.versions: list[str] = []  # every model@version that served work
        self.history: list[tuple[str, float]] = [(QUEUED, t_rel)]
        self.cancel = False
        self.resumed = False
        self.created_at = time.monotonic()
        self.started_at: float | None = None
        self.finished_at: float | None = None
        self.source = source  # "upload" | "dir"
        # Overload accounting: the tenant whose token bucket this job's
        # batches charge, and the job-vs-job scheduling weight (the single
        # runner picks the highest-weight QUEUED job; FIFO within equal).
        self.tenant = tenant or "default"
        self.weight = float(weight)

    @property
    def results_path(self) -> Path:
        return self.dir / "results.jsonl"

    def snapshot(self) -> dict:
        now = time.monotonic()
        end = self.finished_at if self.finished_at is not None else now
        return {
            "id": self.id,
            "state": self.state,
            "model": self.model,
            "topk": self.topk,
            "source": self.source,
            "tenant": self.tenant,
            "weight": self.weight,
            "total": self.total,
            "completed": self.completed,
            "cached": self.cached,
            "errors": self.errors,
            "result_lines": self.result_lines,
            "chunks_done": self.chunks_done,
            "versions": list(self.versions),
            "resumed": self.resumed,
            "age_s": round(now - self.created_at, 1),
            "run_s": (round(end - self.started_at, 2)
                      if self.started_at is not None else None),
            "history": [{"state": s, "t_s": round(t, 3)}
                        for s, t in list(self.history)],
            **({"error": self.error} if self.error else {}),
        }


class _Chunk:
    """One staged slice of a job's manifest: the model version it resolved,
    one slot per image, and the chunk span's decode/cache stamps."""

    __slots__ = ("start", "end", "mv", "slots", "span", "decode_s",
                 "cache_s", "t_staged")

    def __init__(self, start, end, mv, slots, span, decode_s, cache_s):
        self.start = start
        self.end = end
        self.mv = mv
        self.slots = slots
        self.span = span
        self.decode_s = decode_s
        self.cache_s = cache_s
        self.t_staged = time.monotonic()


class _StageTimes:
    """What one item's staging hands ``stage_image`` for a span: the two
    stage totals, which the chunk's span receives as ``job_decode`` and
    ``job_cache_lookup`` (a chunk's items stage on pool threads before the
    chunk's span exists)."""

    __slots__ = ("image_decode", "cache_lookup")

    def __init__(self):
        self.image_decode = self.cache_lookup = 0.0

    def add(self, stage: str, dur_s: float) -> None:
        setattr(self, stage, getattr(self, stage) + dur_s)


# ------------------------------------------------------------ the manager


class JobManager:
    """Owns every job, the persistence under ``jobs_dir``, and the one
    background runner thread (jobs execute FIFO — bulk work is batch
    work; parallel jobs would just interleave on the same gated device
    budget).

    Engine-agnostic by the same seams the registry has: everything device
    flows through ``registry.acquire(...)`` → the version's batcher, so
    mock-engine tests drive the full lifecycle with no JAX.
    """

    def __init__(self, registry, cache, server_cfg, obs=None):
        self.registry = registry
        self.cache = cache
        self.obs = obs
        self.cfg = server_cfg
        self.dir = Path(getattr(server_cfg, "jobs_dir", None) or "jobs")
        self.dir.mkdir(parents=True, exist_ok=True)
        self.bulk_batch = max(1, int(getattr(server_cfg, "jobs_batch", 256)))
        self.max_inflight = max(1, int(
            getattr(server_cfg, "jobs_max_inflight", 2)))
        self.max_items = int(getattr(server_cfg, "jobs_max_items", 100_000))
        # Per-chunk await bound: bulk is throughput traffic, so the bound
        # is generous; a chunk that cannot finish inside it retries its
        # stragglers individually, then records error lines.
        self.await_timeout_s = max(60.0, getattr(
            server_cfg, "request_timeout_s", 30.0) * 4)
        # Chunk staging parallelism: decode-into-slab is CPU work the
        # interactive path spreads across the whole HTTP worker pool; a
        # single-threaded runner would cap job throughput at one core's
        # decode rate. Lease/cache calls are thread-safe by design.
        # Capped at 4: decode is ~0.1 ms/image, so 4 threads stage a
        # 256-chunk in ~10 ms — more would just steal cycles from the
        # interactive handlers the bulk class promises not to crowd.
        self.decode_threads = max(1, int(
            getattr(server_cfg, "jobs_decode_threads", 0)
            or min(4, os.cpu_count() or 4)))
        self._decode_pool: ThreadPoolExecutor | None = None
        self._cond = named_condition("jobs.cond")
        self._jobs: dict[str, Job] = {}
        self._order: list[str] = []  # submission order (queue + listing)
        self._seq = 0
        self._running = True
        self._runner: threading.Thread | None = None
        self._t0 = time.monotonic()
        # Aggregate counters for /stats + /metrics.
        self._images_total = 0
        self._cached_total = 0
        self._errors_total = 0
        self._chunks_total = 0
        # A hot-swap must pause-and-re-version running jobs, not fail them:
        # the retire listener fires under registry.cond at the DRAINING
        # flip (registry.cond → jobs.cond is the declared lock-order
        # climb); the serving listener wakes paused jobs the moment a
        # successor version goes live.
        if hasattr(registry, "add_retire_listener"):
            registry.add_retire_listener(self._on_retire)
        if hasattr(registry, "add_serving_listener"):
            registry.add_serving_listener(self._on_serving)
        self._recover()

    # -------------------------------------------------------------- submit

    def submit_upload(self, files: list[tuple[str, bytes]], model: str | None,
                      topk: int | None, tenant: str = "default",
                      weight: float = 1.0) -> Job:
        """Register an uploaded manifest: every file part spools to the
        job's ``input/`` directory first (the job must survive a server
        restart, so the server cannot depend on the request body)."""
        if not files:
            raise ValueError("job upload carries no file parts")
        if len(files) > self.max_items:
            # Refuse loudly: a silent truncation would 202 and later
            # report DONE while images past the cap were never processed.
            raise ValueError(
                f"manifest of {len(files)} items exceeds the "
                f"jobs_max_items cap ({self.max_items}); split the job"
            )
        model = self._check_model(model)
        job_id, job_dir, seq = self._new_job_dir()
        input_dir = job_dir / "input"
        input_dir.mkdir(parents=True, exist_ok=True)
        items = []
        for i, (name, data) in enumerate(files):
            safe = _SAFE_NAME.sub("_", name or "img")[-80:] or "img"
            p = input_dir / f"{i:06d}_{safe}"
            p.write_bytes(data)
            items.append({"name": name or safe, "path": str(p)})
        return self._register(job_id, seq, job_dir, model, topk, items,
                              "upload", tenant=tenant, weight=weight)

    def submit_dir(self, src: str, model: str | None, topk: int | None,
                   glob: str = "*", recursive: bool = False,
                   tenant: str = "default", weight: float = 1.0) -> Job:
        """Register a server-side directory manifest (the re-index-a-corpus
        shape: the images already live next to the server, so nothing is
        copied — the manifest records paths). Same trust model as the
        admin /models routes: deploy behind the same network boundary."""
        model = self._check_model(model)
        root = Path(src)
        if not root.is_dir():
            raise ValueError(f"not a directory: {src}")
        it = root.rglob(glob) if recursive else root.glob(glob)
        paths = sorted(
            p for p in it
            if p.is_file() and p.suffix.lower() in _IMAGE_SUFFIXES
        )
        if len(paths) > self.max_items:
            raise ValueError(
                f"{len(paths)} images under {src} exceed the "
                f"jobs_max_items cap ({self.max_items}); narrow the glob "
                f"or split the job"
            )
        if not paths:
            raise ValueError(
                f"no images matching {glob!r} under {src} "
                f"(extensions: {', '.join(_IMAGE_SUFFIXES)})"
            )
        job_id, job_dir, seq = self._new_job_dir()
        items = [{"name": str(p.relative_to(root)), "path": str(p)}
                 for p in paths]
        return self._register(job_id, seq, job_dir, model, topk, items, "dir",
                              tenant=tenant, weight=weight)

    def _check_model(self, model: str | None) -> str:
        """Validate the model NAME at submit time (unknown → 404 now, not a
        FAILED job later). Version pins are refused: a job outlives
        versions by design — pinning would make every hot-swap fatal."""
        model = model or self.registry.default_model
        if not model:
            raise UnknownModel("no model given and no default model")
        if "@" in model:
            raise ValueError(
                f"jobs take a model NAME, not a pinned version ({model!r}): "
                "a job survives hot-swaps by re-versioning its remaining work"
            )
        try:
            mv = self.registry.acquire(model)
            self.registry.release(mv)
        except ModelNotServing:
            pass  # exists but between versions: the job will wait/PAUSE
        return model

    def _new_job_dir(self) -> tuple[str, Path, int]:
        with self._cond:
            self._seq += 1
            seq = self._seq
        # urandom suffix: ids must stay unique across restarts without a
        # wall-clock read (the monotonic-clock invariant holds here too).
        job_id = f"j{seq:05d}-{os.urandom(3).hex()}"
        d = self.dir / job_id
        d.mkdir(parents=True, exist_ok=True)
        return job_id, d, seq

    def _register(self, job_id, seq, job_dir, model, topk, items,
                  source, tenant: str = "default",
                  weight: float = 1.0) -> Job:
        job = Job(job_id, seq, job_dir, model, topk, items, source,
                  time.monotonic() - self._t0, tenant=tenant, weight=weight)
        self._write_json(job_dir / "manifest.json", {
            "id": job_id, "seq": seq, "model": model, "topk": topk,
            "source": source, "items": items, "tenant": job.tenant,
            "weight": job.weight,
        })
        self._persist_checkpoint(job)
        with self._cond:
            self._jobs[job_id] = job
            self._order.append(job_id)
            self._ensure_runner_locked()
            self._cond.notify_all()
        log.info("job %s registered: %d images, model=%s, source=%s",
                 job_id, job.total, model, source)
        return job

    # --------------------------------------------------------- persistence

    @staticmethod
    def _write_json(path: Path, doc: dict):
        tmp = path.with_name(path.name + ".tmp")
        tmp.write_text(json.dumps(doc, indent=1))
        os.replace(tmp, path)

    def _persist_checkpoint(self, job: Job):
        """Durable progress record. PAUSED is transient (a paused job is
        just a running job waiting for a version) and persists as RUNNING;
        everything else persists as-is."""
        with self._cond:
            doc = {
                "state": RUNNING if job.state == PAUSED else job.state,
                "completed": job.completed,
                "cached": job.cached,
                "errors": job.errors,
                "result_lines": job.result_lines,
                "result_bytes": job.result_bytes,
                "chunks_done": job.chunks_done,
                "versions": list(job.versions),
                "error": job.error,
            }
        self._write_json(job.dir / "checkpoint.json", doc)

    def _recover(self):
        """Scan ``jobs_dir`` at construction: terminal jobs re-register for
        listing/result streaming; interrupted ones (persisted QUEUED or
        RUNNING — a crash or SIGTERM mid-run) truncate any over-appended
        results back to the checkpoint and re-queue from it."""
        found = []
        for d in self.dir.iterdir() if self.dir.is_dir() else ():
            mf = d / "manifest.json"
            if not mf.is_file():
                continue
            try:
                man = json.loads(mf.read_text())
            except (ValueError, OSError):
                log.exception("unreadable job manifest %s (skipped)", d)
                continue
            # The checkpoint parses in its OWN try: a torn/zero-length
            # checkpoint.json (crash between os.replace metadata and data
            # blocks) must degrade to replay-from-scratch — never skip a
            # job whose manifest and fsync'd results are intact.
            cp = {}
            cpf = d / "checkpoint.json"
            try:
                if cpf.is_file():
                    cp = json.loads(cpf.read_text())
            except (ValueError, OSError):
                log.warning("corrupt checkpoint in %s: job %s replays "
                            "from scratch", d, man.get("id"))
            try:
                found.append((int(man.get("seq", 0)), d, man, cp))
            except (TypeError, ValueError):
                log.exception("unreadable job dir %s (skipped)", d)
        for seq, d, man, cp in sorted(found):
            try:
                weight = float(man.get("weight", 1.0))
            except (TypeError, ValueError):
                weight = 1.0
            job = Job(man["id"], seq, d, man.get("model"), man.get("topk"),
                      list(man.get("items", [])), man.get("source", "dir"),
                      time.monotonic() - self._t0,
                      tenant=str(man.get("tenant") or "default"),
                      weight=weight)
            state = cp.get("state", QUEUED)
            job.completed = int(cp.get("completed", 0))
            job.cached = int(cp.get("cached", 0))
            job.errors = int(cp.get("errors", 0))
            job.result_lines = int(cp.get("result_lines", 0))
            job.result_bytes = int(cp.get("result_bytes", 0))
            job.chunks_done = int(cp.get("chunks_done", 0))
            job.versions = list(cp.get("versions", []))
            job.error = cp.get("error")
            if state in TERMINAL:
                job.state = state
                job.items = []  # listing/streaming never needs the manifest
                job.history.append((state, time.monotonic() - self._t0))
                self._build_line_index(job)
            else:
                # Resume: drop result lines past the checkpoint (a crash
                # between append and checkpoint re-runs that chunk — the
                # truncation is what makes re-running dup-free).
                self._truncate_results(job)
                self._build_line_index(job)
                job.resumed = True
                log.info("job %s resumes from checkpoint: %d/%d images",
                         job.id, job.completed, job.total)
            with self._cond:
                self._jobs[job.id] = job
                self._order.append(job.id)
                self._seq = max(self._seq, seq)
                if job.state not in TERMINAL:
                    self._ensure_runner_locked()
                self._cond.notify_all()

    def _build_line_index(self, job: Job):
        """One startup scan over a restored job's results file rebuilds the
        line→byte index (new lines extend it incrementally as they spool);
        runs from the constructor, before any reader exists."""
        job.line_index = []
        if job.result_lines == 0 or not job.results_path.exists():
            return
        off = 0
        with open(job.results_path, "rb") as f:
            for line in f:
                if len(job.line_index) >= job.result_lines:
                    break
                job.line_index.append(off)
                off += len(line)

    def _truncate_results(self, job: Job):
        p = job.results_path
        if not p.exists():
            job.result_lines = job.result_bytes = 0
            job.completed = job.cached = job.errors = job.chunks_done = 0
            return
        size = p.stat().st_size
        if size > job.result_bytes:
            with open(p, "ab") as f:
                f.truncate(job.result_bytes)
        elif size < job.result_bytes:
            # The results file is SHORTER than the checkpoint claims (lost
            # writes, manual tampering): trust the file, replay from its
            # line count — still no dup, possibly recomputed work.
            lines = p.read_bytes().splitlines()
            job.result_bytes = size
            job.result_lines = len(lines)
            job.completed = min(job.completed, job.result_lines)

    # ------------------------------------------------------------- queries

    # NOTE: method names here avoid ubiquitous call names (get/cancel/...):
    # twdlint's name-based call resolution would otherwise attribute every
    # dict.get()/future.cancel() in the tree to these lock-taking methods.

    def _job(self, job_id: str) -> Job:
        with self._cond:
            job = self._jobs.get(job_id)
        if job is None:
            raise UnknownJob(f"unknown job '{job_id}'")
        return job

    def get_job(self, job_id: str) -> dict:
        return self._job(job_id).snapshot()

    def list_jobs(self) -> list[dict]:
        with self._cond:
            order = list(self._order)
            jobs = dict(self._jobs)
        return [jobs[i].snapshot() for i in order if i in jobs]

    def read_results(self, job_id: str, offset: int = 0, limit: int = 10_000,
                     wait_s: float = 0.0):
        """Result lines from ``offset`` on (at most ``limit``), as raw
        bytes lines. With ``wait_s`` and nothing new yet, blocks until
        more results land or the job reaches a terminal state — the
        long-poll half of incremental streaming. Returns ``(lines,
        next_offset, state, total_lines)``."""
        job = self._job(job_id)
        offset = max(0, int(offset))
        # Lower clamp: limit<=0 would return zero lines with an unchanged
        # next-offset, trapping an offset-following client in a poll loop
        # that can never reach X-Job-Complete.
        limit = max(1, int(limit))
        deadline = time.monotonic() + max(0.0, wait_s)
        with self._cond:
            while (job.result_lines <= offset and job.state not in TERMINAL):
                remaining = deadline - time.monotonic()
                if remaining <= 0:
                    break
                self._cond.wait(timeout=min(0.5, remaining))
            state = job.state
            have = job.result_lines
        lines: list[bytes] = []
        if have > offset:
            # Serve only checkpoint-covered lines: bytes past the counter
            # exist transiently mid-append and could be truncated by a
            # crash-recovery — a client must never hold a line the server
            # would replay.
            want = min(limit, have - offset)
            with open(job.results_path, "rb") as f:
                # Entries below ``have`` are immutable once published (the
                # spool extends the index before bumping result_lines under
                # the condition), so one seek replaces an O(result_lines)
                # line scan per poll. The enumerate fallback only covers a
                # job restored by code that predates the index.
                if offset < len(job.line_index):
                    f.seek(job.line_index[offset])
                    for line in f:
                        if len(lines) >= want:
                            break
                        lines.append(line.rstrip(b"\n"))
                else:
                    for i, line in enumerate(f):
                        if i < offset:
                            continue
                        if len(lines) >= want:
                            break
                        lines.append(line.rstrip(b"\n"))
        return lines, offset + len(lines), state, have

    def stats(self) -> dict:
        """The ``/stats`` "jobs" block (and /metrics' source)."""
        with self._cond:
            by_state: dict[str, int] = {}
            for job in self._jobs.values():
                by_state[job.state] = by_state.get(job.state, 0) + 1
            recent = [self._jobs[i] for i in self._order[-20:]
                      if i in self._jobs]
            return {
                "enabled": True,
                "dir": str(self.dir),
                "bulk_batch": self.bulk_batch,
                "max_inflight": self.max_inflight,
                "by_state": by_state,
                "active": by_state.get(RUNNING, 0) + by_state.get(PAUSED, 0),
                "images_done_total": self._images_total,
                "images_cached_total": self._cached_total,
                "image_errors_total": self._errors_total,
                "chunks_total": self._chunks_total,
                "jobs": [j.snapshot() for j in recent],
            }

    # -------------------------------------------------------------- cancel

    def cancel_job(self, job_id: str) -> dict:
        """Cancel a job. QUEUED cancels immediately; RUNNING/PAUSED set the
        flag and the runner finalizes at its next boundary — completed
        chunks stay spooled (and streamable), nothing past them runs."""
        job = self._job(job_id)
        persist = False
        with self._cond:
            if job.state in TERMINAL:
                pass
            elif job.state == QUEUED:
                self._set_state_locked(job, CANCELLED)
                persist = True
            else:
                job.cancel = True
                self._cond.notify_all()
        if persist:
            self._persist_checkpoint(job)
        return job.snapshot()

    # ----------------------------------------------------------- lifecycle

    def _set_state_locked(self, job: Job, state: str, error: str | None = None):
        if state not in _TRANSITIONS[job.state]:
            raise RuntimeError(
                f"illegal job transition {job.id}: {job.state} -> {state}"
            )
        job.state = state
        if error is not None:
            job.error = error
        if state == RUNNING and job.started_at is None:
            job.started_at = time.monotonic()
        if state in TERMINAL:
            job.finished_at = time.monotonic()
            # A terminal job is only ever listed and result-streamed —
            # neither needs the manifest. Dropping it bounds long-lived
            # memory (recurring 100k-item jobs would otherwise pin every
            # run's item dicts forever; manifest.json keeps the record).
            job.items = []
        job.history.append((state, time.monotonic() - self._t0))
        self._cond.notify_all()

    def _finalize(self, job: Job, state: str, error: str | None = None):
        with self._cond:
            if job.state in TERMINAL:
                return
            if job.state == PAUSED and state == DONE:
                # The drain paused the job while its LAST chunk was in
                # flight: the chunk finished against the old version, so
                # there was no next acquire to flip it back — resume-then-
                # finish keeps the history honest and the machine legal.
                self._set_state_locked(job, RUNNING)
            self._set_state_locked(job, state, error)
        self._persist_checkpoint(job)
        log.info("job %s %s (%d/%d images, %d cached, %d errors)",
                 job.id, state, job.completed, job.total, job.cached,
                 job.errors)

    def _on_retire(self, name, version):
        # Under registry.cond (rank above jobs.cond — a declared climb).
        # Flag flips only: listeners must never block.
        with self._cond:
            for job in self._jobs.values():
                if job.state == RUNNING and job.model == name:
                    self._set_state_locked(job, PAUSED)
            self._cond.notify_all()

    def _on_serving(self, name, version):
        with self._cond:
            self._cond.notify_all()  # wake paused jobs' re-acquire loop

    # --------------------------------------------------------------- runner

    def _ensure_runner_locked(self):
        if self._runner is None or not self._runner.is_alive():
            self._runner = threading.Thread(
                target=self._run_loop, name="job-runner", daemon=True
            )
            self._runner.start()

    def _next_job(self) -> Job | None:
        with self._cond:
            while True:
                if not self._running:
                    return None
                # Weighted pick: highest job weight first, FIFO within
                # equal weight (the _order scan preserves submit order, so
                # max() on (-weight) ties break to the earliest job). Jobs
                # run whole-job-at-a-time on the single runner — weight is
                # job-vs-job priority, not a bandwidth share.
                best = None
                for jid in self._order:
                    job = self._jobs.get(jid)
                    if job is None or job.state != QUEUED:
                        continue
                    if job.cancel:
                        self._set_state_locked(job, CANCELLED)
                        continue
                    if best is None or job.weight > best.weight:
                        best = job
                if best is not None:
                    self._set_state_locked(best, RUNNING)
                    return best
                self._cond.wait(timeout=0.5)

    def _run_loop(self):
        while True:
            job = self._next_job()
            if job is None:
                return
            self._persist_checkpoint(job)  # durable RUNNING marker
            try:
                self._run_job(job)
            except Exception as e:
                # Job-level isolation: one poisoned manifest must not kill
                # the runner for every queued job behind it.
                log.exception("job %s failed", job.id)
                try:
                    self._finalize(job, FAILED,
                                   f"{type(e).__name__}: {e}"[:500])
                except Exception:
                    log.exception("job %s could not finalize", job.id)

    def _should_stop(self, job: Job) -> bool:
        with self._cond:
            return not self._running or job.cancel

    def _run_job(self, job: Job):
        """Drive one manifest: stage up to ``max_inflight`` chunks ahead
        (decode of chunk N+1 overlaps device execution of chunk N, the
        same dataflow shape as the interactive pipeline), finish them in
        order, checkpoint each. Stop/cancel break at chunk boundaries;
        already-staged chunks are aborted un-spooled — they replay on
        resume, which is exactly why spooling is the atom of progress."""
        window: deque[_Chunk] = deque()
        next_idx = job.completed
        interrupted = False
        while True:
            if self._should_stop(job):
                interrupted = True
                break
            if next_idx < job.total and len(window) < self.max_inflight:
                ch = self._stage_chunk(job, next_idx)
                if ch is None:
                    interrupted = True
                    break
                window.append(ch)
                next_idx = ch.end
            elif window:
                if not self._finish_chunk(job, window.popleft()):
                    interrupted = True
                    break
            else:
                break
        for ch in window:
            self._abort_chunk(ch, RuntimeError("job interrupted"))
        if not interrupted:
            self._finalize(job, DONE)
            return
        with self._cond:
            cancelled = job.cancel
        if cancelled:
            self._finalize(job, CANCELLED)
        else:
            # Manager stopping (shutdown): leave the job RUNNING with its
            # last chunk checkpoint durable — the restart resumes it.
            self._persist_checkpoint(job)
            log.info("job %s checkpointed at %d/%d for shutdown",
                     job.id, job.completed, job.total)

    # -------------------------------------------------------------- staging

    def _acquire_serving(self, job: Job):
        """Resolve the job's model to a SERVING version, PAUSING the job
        while none exists (the hot-swap window, or an unload awaiting its
        replacement). Returns None on cancel/stop; FAILS the job if the
        model name disappears from the registry entirely."""
        while True:
            with self._cond:
                if not self._running or job.cancel:
                    return None
            try:
                mv = self.registry.acquire(job.model)
            except ModelNotServing:
                with self._cond:
                    if not self._running or job.cancel:
                        return None
                    if job.state == RUNNING:
                        self._set_state_locked(job, PAUSED)
                        log.info("job %s paused: model '%s' has no serving "
                                 "version (drain in progress?)",
                                 job.id, job.model)
                    self._cond.wait(timeout=0.25)
                continue
            except UnknownModel as e:
                self._finalize(job, FAILED, str(e))
                return None
            except RuntimeError:
                return None  # registry stopped: shutdown path
            resumed = False
            abort = False
            with self._cond:
                if not self._running or job.cancel:
                    abort = True
                else:
                    if job.state == PAUSED:
                        self._set_state_locked(job, RUNNING)
                        resumed = True
                    if mv.ref not in job.versions:
                        job.versions.append(mv.ref)
            if abort:
                self.registry.release(mv)
                return None
            if resumed:
                log.info("job %s resumed on %s", job.id, mv.ref)
            return mv

    def _stage_chunk(self, job: Job, start: int) -> _Chunk | None:
        """Decode + cache-consult + bulk-lease one chunk of the manifest.
        Returns None on cancel/stop (partial staging unwound)."""
        while True:
            mv = self._acquire_serving(job)
            if mv is None:
                return None
            batcher = mv.batcher
            if batcher is not None:
                break
            # Resolved mid-teardown (batcher already detached): give the
            # ref back and re-resolve — bounded by cancel/stop.
            self.registry.release(mv)
            if self._should_stop(job):
                return None
            time.sleep(0.05)
        end = min(job.total, start + self.bulk_batch)
        topk = clamp_topk(job.topk, mv.model_cfg)
        if self._decode_pool is None and self.decode_threads > 1:
            self._decode_pool = ThreadPoolExecutor(
                max_workers=self.decode_threads,
                thread_name_prefix="job-decode")
        slots: list[tuple] = []
        decode_s = cache_s = 0.0
        try:
            if self._decode_pool is not None and end - start > 1:
                # Parallel staging: decode is the chunk's CPU cost and the
                # interactive path amortizes it across the whole HTTP
                # pool — a serial runner would cap job throughput at one
                # core's decode rate. Order is preserved (slots[i] is
                # item start+i); cancel lands at the chunk boundary.
                futs = [
                    self._decode_pool.submit(
                        self._stage_item, mv, batcher, job.items[i], topk,
                        job.tenant)
                    for i in range(start, end)
                ]
                for fi, f in enumerate(futs):
                    try:
                        slot, d_s, c_s = f.result()
                    except Exception:
                        # Siblings still in the pool keep staging after
                        # this raise — they take bulk leases and lead
                        # cache flights. Drain them into ``slots`` so the
                        # unwind below releases/aborts them too; otherwise
                        # their flights wedge every coalesced interactive
                        # waiter on those keys until request timeout.
                        for g in futs[fi + 1:]:
                            try:
                                slots.append(g.result()[0])
                            except Exception:
                                pass
                        raise
                    decode_s += d_s
                    cache_s += c_s
                    slots.append(slot)
            else:
                for i in range(start, end):
                    if self._should_stop(job):
                        abort_slots(slots, self.cache,
                                    RuntimeError("job interrupted"))
                        self.registry.release(mv)
                        return None
                    slot, d_s, c_s = self._stage_item(mv, batcher,
                                                      job.items[i], topk,
                                                      job.tenant)
                    decode_s += d_s
                    cache_s += c_s
                    slots.append(slot)
        except Exception as e:
            abort_slots(slots, self.cache, e)
            self.registry.release(mv)
            raise
        # Seal whatever this chunk left open: a full chunk already sealed
        # at bulk capacity (no-op), the manifest's partial tail must not
        # wait out the bulk window's backstop deadline.
        if hasattr(batcher, "flush_bulk"):
            batcher.flush_bulk()
        # The chunk span is created only once staging committed (earlier
        # exits have nothing to report, and every created Span must reach
        # obs.finish — the Span→finish pairing invariant).
        span = Span()
        span.note("job", job.id)
        span.note("chunk_start", start)
        # Bulk traffic class, explicit: /debug/slow and the trace export
        # must never mix background chunk spans into interactive forensics.
        span.note("class", "bulk")
        span.add("job_decode", decode_s)
        if cache_s:
            span.add("job_cache_lookup", cache_s)
        return _Chunk(start, end, mv, slots, span, decode_s, cache_s)

    def _stage_item(self, mv, batcher, item: dict, topk: int,
                    tenant: str = "default"):
        """One manifest item → ``(slot, decode seconds, lookup seconds)``
        (decode-pool worker body). The slot is serving/staging.py's —
        staged as BULK: bulk builders, bulk cache counters, the same keys
        as the interactive path — or ``("err", msg)`` for a file that
        cannot be read or decoded (the job still finishes, the error
        counted per image), or ``("retry",)`` when the batcher is shutting
        down under us (hot-swap drain racing the staging): the item is
        deferred to the retry path instead of failing the whole job."""
        try:
            data = Path(item["path"]).read_bytes()
        except OSError as e:
            return ("err", f"read failed: {e}"), 0.0, 0.0
        cache = self.cache
        if cache is not None and not cache.enabled:
            cache = None
        times = _StageTimes()
        try:
            slot = stage_image(
                data, batcher=batcher, mv=mv, cache=cache, topk=topk,
                buckets=self.cfg.canvas_buckets, span=times, bulk=True,
                tenant=tenant, chaos=getattr(self.registry, "chaos", None))
        except UndecodableImage as e:
            slot = ("err", str(e))
        except ShuttingDownError:
            slot = ("retry",)
        return slot, times.image_decode, times.cache_lookup

    def _abort_chunk(self, ch: _Chunk, exc: BaseException):
        abort_slots(ch.slots, self.cache, exc)
        self.registry.release(ch.mv)

    # ------------------------------------------------------------ finishing

    def _finish_chunk(self, job: Job, ch: _Chunk) -> bool:
        """Await one staged chunk, retry stragglers whose batch died under
        a hot-swap/shutdown against the (new) serving version, spool the
        chunk's result lines, checkpoint. Returns False when the chunk
        could not complete (manager stopping / job cancelled) — in that
        case NOTHING of it is spooled, so resume replays it dup-free."""
        mv = ch.mv
        topk = clamp_topk(job.topk, mv.model_cfg)
        n = len(ch.slots)
        payloads: list = [None] * n
        cached = [False] * n
        errs: list = [None] * n
        retry: list[int] = []
        deadline = time.monotonic() + self.await_timeout_s
        t_await0 = time.monotonic()
        try:
            # OWN slots first: leaders must publish to the cache (waking
            # every coalesced waiter, including other requests') before
            # this chunk blocks on any foreign flight.
            for i, slot in enumerate(ch.slots):
                kind = slot[0]
                if kind == "err":
                    errs[i] = slot[1]
                elif kind == "retry":
                    retry.append(i)  # staging lost its batcher mid-drain
                elif kind == "done":
                    payloads[i], cached[i] = slot[1], True
                elif kind == "own":
                    _, future, orig, flight, _lease = slot
                    try:
                        row = future.result(
                            timeout=max(0.0, deadline - time.monotonic())
                        )
                    except BaseException as e:  # noqa: BLE001 — retried below
                        if flight is not None and self.cache is not None:
                            self.cache.abort(flight, e)
                        retry.append(i)
                        continue
                    payload = format_result_row(row, orig, topk, mv,
                                                trace_id=ch.span.trace_id)
                    if flight is not None:
                        self.cache.complete(flight, payload)
                    payloads[i] = payload
            for i, slot in enumerate(ch.slots):
                if slot[0] != "wait":
                    continue
                try:
                    payload, _etag = slot[1].future.result(
                        timeout=max(0.0, deadline - time.monotonic())
                    )
                except BaseException:  # noqa: BLE001 — flight retired/failed
                    retry.append(i)
                    continue
                payloads[i], cached[i] = payload, True
        finally:
            self.registry.release(mv)
        # Stragglers: their batch died under them (hot-swap drain, batcher
        # stop, expired lease, chunk timeout). Re-resolve the model — the
        # NEW version after a swap — and compute each individually; only a
        # repeated hard failure becomes an error line. Zero lost images.
        for i in sorted(retry):
            out = self._retry_item(job, job.items[ch.start + i])
            if out is None:
                return False  # stopping/cancelled: chunk stays un-spooled
            payloads[i], cached[i], errs[i] = out
        ch.span.add("job_await", time.monotonic() - t_await0)
        t_spool = time.monotonic()
        lines = []
        n_err = 0
        for i in range(n):
            item = job.items[ch.start + i]
            rec = {"i": ch.start + i, "name": item["name"]}
            if errs[i] is not None and payloads[i] is None:
                rec["error"] = str(errs[i])
                rec["trace_id"] = ch.span.trace_id
                n_err += 1
            else:
                rec.update(payloads[i])
                if cached[i]:
                    rec["cached"] = True
                # Cache-served payloads may predate trace stamping (an
                # interactive leader computed them): the chunk's own trace
                # is still the honest join key for THIS row's handling.
                rec.setdefault("trace_id", ch.span.trace_id)
            lines.append(json.dumps(rec))
        encoded = [ln.encode() + b"\n" for ln in lines]
        blob = b"".join(encoded)
        # Start offsets of this chunk's lines, appended to the job's line
        # index in the SAME locked block that bumps result_lines — readers
        # snapshot result_lines under the condition, so every covered line
        # has its offset by the time a poll can ask for it.
        offs = []
        off = job.result_bytes  # runner-only field: stable outside the lock
        for piece in encoded:
            offs.append(off)
            off += len(piece)
        with open(job.results_path, "ab") as f:
            f.write(blob)
            f.flush()
            os.fsync(f.fileno())
        n_cached = sum(cached)
        with self._cond:
            job.line_index.extend(offs)
            job.completed += n
            job.cached += n_cached
            job.errors += n_err
            job.result_lines += n
            job.result_bytes += len(blob)
            job.chunks_done += 1
            self._images_total += n
            self._cached_total += n_cached
            self._errors_total += n_err
            self._chunks_total += 1
            self._cond.notify_all()  # result-stream long-pollers
        self._persist_checkpoint(job)
        ch.span.add("job_spool", time.monotonic() - t_spool)
        ch.span.note("rows", n)
        ch.span.note("cached", n_cached)
        if self.obs is not None:
            self.obs.finish(ch.span, 200)
        return True

    def _retry_item(self, job: Job, item: dict):
        """Individually recompute one straggler. Returns (payload, cached,
        err) or None when the manager is stopping / the job cancelled."""
        last: BaseException | None = None
        for _attempt in range(3):
            mv = self._acquire_serving(job)
            if mv is None:
                return None
            batcher = mv.batcher
            if batcher is None:
                self.registry.release(mv)
                time.sleep(0.05)
                continue
            topk = clamp_topk(job.topk, mv.model_cfg)
            slot = None
            try:
                slot, _d, _c = self._stage_item(mv, batcher, item, topk,
                                                job.tenant)
                kind = slot[0]
                if kind == "err":
                    return (None, False, slot[1])
                if kind == "retry":
                    last = ShuttingDownError("batcher shutting down")
                    continue
                if kind == "done":
                    return (slot[1], True, None)
                if kind == "wait":
                    payload, _etag = slot[1].future.result(
                        timeout=self.await_timeout_s)
                    return (payload, True, None)
                _, future, orig, flight, _lease = slot
                row = future.result(timeout=self.await_timeout_s)
                # Straggler retries run outside any chunk span; the spool
                # loop's setdefault stamps the chunk trace on the row.
                payload = format_result_row(row, orig, topk, mv)
                if flight is not None:
                    self.cache.complete(flight, payload)
                return (payload, False, None)
            except Exception as e:  # noqa: BLE001 — every attempt bounded
                last = e
                if slot is not None:
                    abort_slots([slot], self.cache, e)
            finally:
                self.registry.release(mv)
        return (None, False,
                f"retries exhausted: {type(last).__name__}: {last}")

    # ----------------------------------------------------------------- stop

    def stop(self, grace_s: float = 10.0):
        """Shutdown: the runner finishes (and checkpoints) its current
        chunk window, aborts anything past it, and exits — the SIGTERM
        half of "a restart resumes from the last checkpoint". Call BEFORE
        the registry stops: in-flight bulk futures need live batchers to
        resolve inside the grace."""
        with self._cond:
            self._running = False
            self._cond.notify_all()
            runner = self._runner
        if runner is not None and runner.is_alive():
            runner.join(timeout=grace_s)
            if runner.is_alive():
                log.warning(
                    "job runner still busy after %.1fs grace; progress is "
                    "bounded by the last durable chunk checkpoint", grace_s
                )
        pool = self._decode_pool
        if pool is not None and (runner is None or not runner.is_alive()):
            pool.shutdown(wait=False, cancel_futures=True)
