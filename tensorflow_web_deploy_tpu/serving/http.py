"""HTTP surface: dependency-free WSGI app + pooled HTTP/1.1 keep-alive server.

The reference exposes one Flask route — ``POST /predict`` with an uploaded
image, JSON top-k response, plus an HTML upload page (SURVEY.md §1 L3, §2
C2/C7). Flask is not available in this environment (SURVEY.md §7 noted the
fallback), so the same surface is a plain WSGI app served by a small
stdlib-only front end built for the serving hot path:

- **HTTP/1.1 keep-alive, worker pool.** The old wsgiref front end spoke
  HTTP/1.0 with ``Connection: close`` and spawned one thread per
  connection, so a closed-loop client paid a TCP handshake + thread spawn
  per image — host overhead that swamped the device (BENCH_r05: ~225 img/s
  through /predict vs ~5,450 device-resident). Here a fixed pool of worker
  threads owns connections for their whole lifetime and serves any number
  of requests per connection; the accept loop only enqueues. The GIL is
  irrelevant because all device work happens on the batcher's dispatcher
  thread anyway.
- **Connection-reuse counters** (connections vs requests) exported via
  ``/stats`` so keep-alive effectiveness is visible without a profiler.
- **One way in for an image** (serving/staging.py ``stage_image``, which
  the bulk job runner calls too). This module loops over a request's
  files and maps what staging raises to a status; which header probe,
  lease, native decode and PIL fallback go together on the wire the
  batcher speaks, the order cache lookup (keyed by the upload's bytes) →
  lease → decode into the leased row → commit, and the unwind of a lease
  and a led flight all live there.
- **Request-scoped span tracing.** Every request gets a monotonically
  derived trace ID at accept time (or propagates a well-formed inbound
  ``X-Trace-Id``) and carries a Span (utils/tracing.py) through the whole
  path — header read, body read, slot lease (``lease_wait``),
  decode-into-slab (``image_decode``), staging commit (``staging_write``),
  assembly wait (``queue_wait``), the H2D copy (``device_transfer``), the
  wait behind earlier calls plus the device's work (``device_execute``),
  the D2H (``device_d2h``), postprocess, serialize — stamped by this
  module, the batcher, and the engine.
- **Content-addressed response cache + single-flight dedup** (serving/
  respcache.py, ``--cache-bytes``). Staging digests the upload's bytes
  (with the canvas bucket set and the wire) and consults the cache FIRST,
  before the slot lease and any decode: a hit serves the stored payload
  with ``X-Cache: hit`` for a hash of the upload and nothing else; a
  concurrent request for the same bytes coalesces onto the in-flight
  leader's computation (``X-Cache: coalesced`` — a viral image costs one
  decode and one device dispatch instead of N); a miss leads and fills
  the cache. What it gives up: the same pixels in other bytes (metadata
  rewritten without re-encoding) are another entry. Keys
  carry the model VERSION, and the registry invalidates a version's
  entries atomically when it starts draining, so a hot-swap can never
  serve a stale result. Single-image responses carry an ``ETag`` (=
  response digest) and honor ``If-None-Match`` with a bodyless 304.
- **Bounded-queue fast reject.** With ``--max-queue`` set, a model whose
  batcher backlog is at the bound answers 503 + ``Retry-After``
  immediately (the batcher's BacklogFull) instead of queueing the upload
  toward the request timeout; rejections are counted in /stats and
  /metrics. The trace ID comes back in the ``X-Trace-Id`` response header;
  the completed span feeds per-stage histograms (/metrics), the
  slow-request flight recorder (/debug/slow), and the opt-in JSON access
  log.

Routes:
    POST /predict       image (raw body or multipart/form-data) → JSON
                        top-k or detections; ``?topk=N`` for classify;
                        ``?model=name[@version]`` routes to any SERVING
                        model in the registry (default model without it).
                        Several file parts (or ``?batch=1``) →
                        {"results": [...]} in upload order; all parts are
                        submitted together, so same-canvas-bucket images
                        typically share one device dispatch.
    GET  /healthz       1-image device round-trip (SURVEY.md §5.3)
    GET  /models        model registry: default model + every version's
                        lifecycle state, transition history, and stats
    POST /models/load   admin: load a model ({"model": spec, "name"?,
                        "activate"?, "wait"?}) — built+warmed off the
                        request path, serving only after warmup succeeds
    POST /models/swap   admin: hot-swap a model to a new version
                        ({"name"?, "model"?, "wait"?}) with zero downtime
    POST /models/unload admin: drain + unload ({"name", "version"?})
    POST /jobs          bulk offline inference (--jobs-dir): a multipart
                        upload of many images, or a JSON body {"dir":
                        server-side path, "glob"?, "recursive"?} — plus
                        ?model=/?topk= — registers a checkpointed job
                        driven through the batcher's lower-priority bulk
                        class at the throughput batch size; answers 202
                        with the job id
    GET  /jobs          all jobs (state, progress, versions)
    GET  /jobs/{id}     one job's lifecycle + progress document
    GET  /jobs/{id}/results?offset=N[&limit=M][&wait_s=S]
                        JSON-lines results from offset N (one line per
                        image, manifest order); X-Job-Next-Offset is the
                        resume cursor, X-Job-State the live state;
                        wait_s long-polls until more results or a
                        terminal state — incremental streaming that
                        survives client AND server restarts
    POST /jobs/{id}/cancel  stop at the next chunk boundary; completed
                        chunks stay streamable
    GET  /stats         rolling p50/p99, images/sec, batch histogram +
                        occupancy, live adaptive delay, keep-alive
                        counters, per-stage tracing summary, per-model
                        registry block
    GET  /metrics       Prometheus text exposition: counters, gauges,
                        per-stage latency histograms (fixed log buckets),
                        and per-model lifecycle/traffic gauges
    GET  /debug/slow    flight recorder: full span breakdown of the N
                        slowest + N most recent erroring requests
    POST /debug/trace   capture a jax.profiler trace for N ms (§5.1)
    GET  /              minimal HTML upload demo page (reference C7)

The admin POST routes mutate serving state and are as open as the rest of
the surface — deploy behind the same network boundary that already guards
/debug/trace.
"""

from __future__ import annotations

import json
import logging
import queue
import select
import socket
import sys
import threading
import time
import urllib.parse
from concurrent.futures import TimeoutError as FutureTimeout
from http.server import BaseHTTPRequestHandler
from socketserver import TCPServer

from jax.profiler import TraceAnnotation

from ..utils.locks import named_lock
from ..utils.metrics import Observability, PromText, make_access_logger
from ..utils.tracing import (
    Span, accept_trace_id, chrome_trace, clock_marker, effective_window, stage,
)
from . import aotcache, costmodel
from .batcher import BacklogFull, ShuttingDown
from .dag import PipelineCatalog, PipelineUnavailable, parse_pipeline_args
from .jobs import JobManager, UnknownJob, clamp_topk, format_result_row
from .overload import (
    DEFAULT_TENANT, SHED_BACKLOG, SHED_DEADLINE, SHED_DEGRADED, SHED_QUOTA,
    DeadlineExceeded, Degraded, QuotaExceeded, build_admission,
    build_pressure, parse_slo_classes,
)
from .registry import FAILED, ModelNotServing, ModelRegistry, UnknownModel
from .respcache import ResponseCache, payload_etag
from .staging import UndecodableImage, abort_slots, stage_image
from .telemetry import build_hub

log = logging.getLogger("tpu_serve.http")


class _CoalesceRetry(Exception):
    """Internal: a request coalesced onto another request's in-flight
    computation and that flight aborted (typically because its model
    version retired mid-drain). The request re-resolves through the
    registry — landing on the NEW serving version — and retries once as
    an ordinary miss."""

_DEMO_PAGE = """<!doctype html>
<title>tpu-serve</title>
<style>
 body { font-family: system-ui, sans-serif; max-width: 40em; margin: 2em auto; }
 table { border-collapse: collapse; margin-top: 1em; }
 td, th { border: 1px solid #ccc; padding: .3em .8em; text-align: left; }
 #preview { max-width: 20em; max-height: 20em; display: block; margin-top: 1em; }
 #ms { color: #666; }
</style>
<h2>tensorflow_web_deploy_tpu — image inference</h2>
<form id=f>
  <input type=file id=file accept=image/*>
  <button>Predict</button> <span id=ms></span>
</form>
<img id=preview hidden>
<div id=out></div>
<p>POST an image to <code>/predict</code> (raw body or multipart); see
<a href=/stats>/stats</a>, <a href=/healthz>/healthz</a>.</p>
<script>
const f = document.getElementById('f');
f.addEventListener('submit', async (e) => {
  e.preventDefault();
  const file = document.getElementById('file').files[0];
  if (!file) return;
  const img = document.getElementById('preview');
  img.src = URL.createObjectURL(file); img.hidden = false;
  const t0 = performance.now();
  const resp = await fetch('/predict', {method: 'POST', body: file});
  const data = await resp.json();
  document.getElementById('ms').textContent =
      `${(performance.now() - t0).toFixed(0)} ms`;
  // Build result cells with textContent (never innerHTML): labels come
  // from a server-side file and must not be interpretable as markup.
  const preds = data.predictions || data.detections || [];
  const out = document.getElementById('out');
  out.textContent = '';
  if (preds.length) {
    const table = document.createElement('table');
    const hdr = table.insertRow();
    for (const h of ['label', 'score']) {
      const th = document.createElement('th');
      th.textContent = h;
      hdr.appendChild(th);
    }
    for (const p of preds) {
      const tr = table.insertRow();
      tr.insertCell().textContent = String(p.label ?? p.class);
      tr.insertCell().textContent = (p.score ?? 0).toFixed(4);
    }
    out.appendChild(table);
  } else {
    const pre = document.createElement('pre');
    pre.textContent = JSON.stringify(data, null, 2);
    out.appendChild(pre);
  }
});
</script>
"""


def _parse_multipart_files(body: bytes, content_type: str) -> list[tuple[str, bytes]]:
    """Extract ALL file parts from a multipart/form-data body, in order,
    as ``(display_name, payload)`` pairs (name = the part's filename, for
    error messages that point at the right upload).

    Minimal parser (stdlib ``cgi`` is gone in Python 3.12): split on the
    boundary; exactly ONE leading/trailing CRLF frames each part, and only
    that is removed — a blanket strip would eat payload bytes when the
    file's own content ends in 0x0A/0x0D (real for BMP/TIFF/WebP; JPEG is
    safe only because it ends FF D9). When the body has no file part at
    all, fall back to the first plain form field (a bare curl -F without a
    filename still works) — but a text field never shadows a real upload.
    """
    boundary = None
    for piece in content_type.split(";"):
        piece = piece.strip()
        if piece.startswith("boundary="):
            boundary = piece[len("boundary="):].strip('"')
    if not boundary:
        return []
    delim = b"--" + boundary.encode()
    files: list[tuple[str, bytes]] = []
    fallback = None
    for part in body.split(delim):
        if part.startswith(b"\r\n"):
            part = part[2:]
        if part.endswith(b"\r\n"):
            part = part[:-2]
        if not part or part.strip(b"\r\n- ") == b"":
            continue  # preamble / the final "--" terminator
        header_end = part.find(b"\r\n\r\n")
        if header_end < 0:
            continue
        headers = part[:header_end].decode("utf-8", "replace")
        payload = part[header_end + 4 :]
        hl = headers.lower()
        if "content-disposition" not in hl:
            continue
        if "filename=" in hl:
            fname = headers.split("ilename=", 1)[1].split(";")[0].split("\r\n")[0]
            files.append((fname.strip().strip('"'), payload))
        elif fallback is None:
            fallback = ("body", payload)
    if not files and fallback is not None:
        return [fallback]
    return files


def _lifecycle_metrics(p: PromText, life: dict, labels: dict) -> None:
    """``/stats → batcher.lifecycle`` as Prometheus counters with the
    model's labels, one family a unit: batches by seal reason, the phase
    clocks (``open_s_total`` → ``phase="open"``), bytes each way, and every
    other count (``stamps_late_total``, the counters the model's program
    returns: ``tokens_real_total`` → ``counter="tokens_real"``)."""
    for key, value in life.items():
        if key == "by_reason":
            for reason, n in value.items():
                p.scalar("lifecycle_batches_total", n, mtype="counter",
                         labels=dict(labels, reason=reason),
                         help_="Batches launched, by why they sealed.")
        elif key.endswith("_s_total"):
            p.scalar("lifecycle_seconds_total", value, mtype="counter",
                     labels=dict(labels, phase=key[:-len("_s_total")]),
                     help_="Batches' seconds in each phase of their life "
                     "(open, launch_wait, inflight; the flight's h2d, "
                     "device_queue, device, d2h), and the starved and "
                     "h2d_bound clocks.")
        elif key.endswith("_bytes_total"):
            p.scalar("lifecycle_bytes_total", value, mtype="counter",
                     labels=dict(labels, direction=key[:-len("_bytes_total")]),
                     help_="Bytes the batches copied, by direction.")
        elif key.endswith("_total") and key != "batches_total":
            p.scalar("lifecycle_counts_total", value, mtype="counter",
                     labels=dict(labels, counter=key[:-len("_total")]),
                     help_="Every other batcher.lifecycle count: window "
                     "holds, unpack-kernel batches, late stamps, and what "
                     "the model's program counts a call.")


def _qs_last(qs: dict[str, list[str]], key: str) -> str | None:
    """Last value wins for duplicate query keys (the common proxy/browser
    convention); values arrive percent-decoded from parse_qs."""
    vals = qs.get(key)
    return vals[-1] if vals else None


def _etag_matches(inm: str | None, etag: str) -> bool:
    """RFC 9110 ``If-None-Match``: true when any listed entity-tag matches
    ``etag`` (weak comparison — a ``W/`` prefix is ignored) or the header
    is ``*``. The ETag here is a content digest of the formatted payload +
    serving version, so a match means the client's copy is byte-identical
    in every stable field."""
    if not inm:
        return False
    if inm.strip() == "*":
        return True
    for tok in inm.split(","):
        tok = tok.strip()
        if tok[:2] in ("W/", "w/"):
            tok = tok[2:].strip()
        if tok.strip('"') == etag:
            return True
    return False


class App:
    """WSGI application over a model registry.

    The historical single-model constructor shape — ``App(engine, batcher,
    cfg)`` — still works: it wraps the pair into a one-entry
    :class:`~.registry.ModelRegistry`. Multi-model servers construct the
    registry first and use :meth:`from_registry`. Either way every request
    resolves its model through the registry, so a hot-swap changes what
    the very next request runs against with no App-level state to update.
    """

    def __init__(self, engine, batcher, server_cfg, registry: ModelRegistry | None = None):
        if registry is None:
            registry = ModelRegistry.single(engine, batcher, server_cfg)
        self.registry = registry
        self.cfg = server_cfg
        self.http_counters = None  # attached by make_http_server
        # Span aggregation: per-stage histograms, status counters, the
        # slow-request flight recorder. One instance per app — every
        # observability surface (/metrics, /stats tracing, /debug/slow,
        # access log) reads from it. getattr defaults keep embedders that
        # hand-build older ServerConfig-shaped objects working.
        self.obs = Observability(
            recorder_n=getattr(server_cfg, "flight_recorder_n", 32),
            recorder_recent_n=getattr(
                server_cfg, "flight_recorder_recent_n", 512),
            recorder_bytes=getattr(
                server_cfg, "flight_recorder_bytes", 4 << 20),
        )
        access_log = getattr(server_cfg, "access_log", None)
        if access_log:
            self.obs.set_access_log(make_access_logger(access_log))
        # POST /debug/trace: one recording at a time (the flag, under its
        # lock; the recording itself sleeps outside it), and what the last
        # one covered, for /stats "profile".
        self._profile_lock = named_lock("http.profile_lock")
        self._profiling = False
        self._profile: dict | None = None
        # Content-addressed response cache (serving/respcache.py): keyed by
        # (model, version, digest of the decoded canvas, topk, serving
        # dtype), with single-flight dedup. cache_bytes=0 (the dataclass
        # default) disables it — the object still exists so /stats and
        # /metrics always carry the cache block. The registry's retire
        # listener drops a version's entries atomically with its DRAINING
        # flip.
        self.cache = ResponseCache(int(getattr(server_cfg, "cache_bytes", 0) or 0))
        if hasattr(registry, "add_retire_listener"):
            registry.add_retire_listener(self.cache.invalidate)
        # Bulk offline jobs (serving/jobs.py): enabled by --jobs-dir. The
        # manager persists manifests/results/checkpoints there, drives
        # them through the registry's batchers as the bulk traffic class,
        # and resumes interrupted jobs found on disk at construction.
        self.jobs: JobManager | None = None
        if getattr(server_cfg, "jobs_dir", None):
            self.jobs = JobManager(registry, self.cache, server_cfg,
                                   obs=self.obs)
        # Overload engineering (serving/overload.py): the admission
        # controller and chaos injector are registry-owned (shared with
        # every batcher and the job runner); the pressure ladder and SLO
        # class table are HTTP-side concerns and live here. getattr keeps
        # embedders that hand-build registry-shaped objects working.
        self.admission = getattr(registry, "admission", None)
        if self.admission is None:
            self.admission = build_admission(server_cfg)
        self.chaos = getattr(registry, "chaos", None)
        self.pressure = build_pressure(server_cfg)
        self.slo_classes = parse_slo_classes(
            getattr(server_cfg, "slo_classes", None))
        # Telemetry history (serving/telemetry.py): fixed-memory multi-
        # resolution rings + SLO burn-rate alerting + structured events.
        # App-owned lifecycle like the job runner: built here, sampler
        # started here, stopped by shutdown_gracefully. None when
        # --telemetry-interval 0 (every surface degrades gracefully).
        self.telemetry = build_hub(self, server_cfg)
        if self.telemetry is not None:
            self.telemetry.start()
        # Pipeline DAGs (serving/dag.py): compositions served as one
        # device-resident request. Specs validate EAGERLY here — a bad
        # --pipeline fails the boot, never a 500 at first request. The
        # catalog's registry listeners re-resolve a pipeline whenever a
        # stage model hot-swaps. The object always exists (possibly
        # empty) so /pipelines, /stats and /metrics never branch.
        self.pipelines = PipelineCatalog(
            registry, cache=self.cache, hub=self.telemetry,
            max_crops=int(getattr(server_cfg, "pipeline_max_crops", 8)))
        if hasattr(registry, "add_serving_listener"):
            self.pipelines.attach_listeners()
        for spec in parse_pipeline_args(
                getattr(server_cfg, "pipelines", ()) or ()):
            self.pipelines.register(spec)
        if hasattr(registry, "attach_pipelines"):
            registry.attach_pipelines(self.pipelines)
        # Static config echo for /stats, built once from the DEFAULT model's
        # live engine/batcher (their constructors may clamp or override what
        # ServerConfig says), so an operator reading p99 sees the values the
        # dispatcher actually uses. Per-model knobs for non-default models
        # live in the /stats "models" block.
        mv = registry.default_entry()
        engine = mv.engine if mv is not None else None
        batcher = mv.batcher if mv is not None else None
        model_cfg = mv.model_cfg if mv is not None else server_cfg.model
        self._config_echo = {
            "model_source": model_cfg.source,
            "task": model_cfg.task,
            "dtype": model_cfg.dtype,
            "input_size": list(model_cfg.input_size),
            "ckpt_path": model_cfg.ckpt_path,
            "wire_format": self.cfg.wire_format,
            "resize": self.cfg.resize,
            "packed_io": self.cfg.packed_io,
            "canvas_buckets": list(self.cfg.canvas_buckets),
            "cache_bytes": self.cache.max_bytes,
            "jobs_dir": getattr(server_cfg, "jobs_dir", None),
            "pipelines": self.pipelines.names(),
            # Flight-recorder memory bound, explicit: entry caps per board
            # plus the recent-ring byte budget /debug/trace reads from.
            "flight_recorder": {
                "slowest_entries": self.obs.flight.n,
                "recent_entries": self.obs.flight.recent_n,
                "recent_bytes_cap": self.obs.flight.max_bytes,
            },
            "jobs_batch": (self.jobs.bulk_batch if self.jobs else None),
            "jobs_max_inflight": (self.jobs.max_inflight if self.jobs
                                  else None),
            "batch_buckets": list(engine.batch_buckets) if engine is not None else None,
            "max_batch": (batcher.max_batch if batcher
                          else getattr(engine, "max_batch", None)),
            "max_delay_ms": batcher.max_delay_s * 1e3 if batcher else None,
            "adaptive_delay": getattr(batcher, "adaptive_delay", None) if batcher else None,
            "pipeline_depth": getattr(batcher, "pipeline_depth", None) if batcher else None,
            "max_queue": getattr(batcher, "max_queue", None) if batcher else None,
            "devices": (len(engine.mesh.devices.flatten())
                        if engine is not None else None),
            # Default model's mesh placement (strategy + replica count);
            # the live per-version view rides /stats "models" and /models.
            "placement": (engine.placement_summary()
                          if hasattr(engine, "placement_summary") else None),
            # Boot-time default only; the LIVE model list (runtime loads
            # included) is /stats' "models" block and GET /models.
            "default_model": registry.default_model,
        }

    @classmethod
    def from_registry(cls, registry: ModelRegistry, server_cfg) -> "App":
        """Multi-model construction: the registry was built (and its boot
        models adopted) first; the App is just the HTTP surface over it."""
        return cls(None, None, server_cfg, registry=registry)

    # Back-compat handles: the DEFAULT model's live serving unit. Properties
    # (not attributes captured at init) so a hot-swap of the default model
    # retargets every surface that reads them — /healthz must round-trip
    # the engine that is actually serving, not the one from boot.
    @property
    def engine(self):
        mv = self.registry.default_entry()
        return mv.engine if mv is not None else None

    @property
    def batcher(self):
        mv = self.registry.default_entry()
        return mv.batcher if mv is not None else None

    @property
    def model_cfg(self):
        mv = self.registry.default_entry()
        return mv.model_cfg if mv is not None else self.cfg.model

    @property
    def labels(self):
        mv = self.registry.default_entry()
        return mv.labels if mv is not None else []

    def attach_http(self, srv) -> None:
        """Called by make_http_server: expose the live server's counters and
        pool config through /stats."""
        self.http_counters = srv.counters
        self._config_echo.update(
            http_workers=srv.pool_size,
            keepalive_timeout_s=srv.keepalive_timeout_s,
            http_protocol="HTTP/1.1 keep-alive",
        )

    # ------------------------------------------------------------------ wsgi

    def __call__(self, environ, start_response):
        path = environ.get("PATH_INFO", "/")
        method = environ.get("REQUEST_METHOD", "GET")
        # The pooled front end creates the span at accept time (it owns the
        # header-read stage) and finalizes it after the drain, just before
        # the response goes out. Direct WSGI callers (tests, embedders) get
        # the same tracing with an app-owned span finalized here.
        span = environ.get("tpu_serve.span")
        own_span = span is None
        if own_span:
            span = Span(accept_trace_id(environ.get("HTTP_X_TRACE_ID")))
            environ["tpu_serve.span"] = span
        span.note_default("method", method)
        span.note_default("path", path)
        # Route handlers return (status, body, ctype) and may append a 4th
        # element: extra response headers (e.g. Retry-After on a 503
        # backlog rejection).
        extra_headers: list[tuple[str, str]] = []
        try:
            if path == "/predict" and method == "POST":
                res = self._predict(environ)
                status, body, ctype = res[0], res[1], res[2]
                if len(res) > 3 and res[3]:
                    extra_headers = list(res[3])
            elif path == "/healthz":
                engine = self.engine
                ok = engine is not None and engine.healthcheck()
                status = "200 OK" if ok else "503 Service Unavailable"
                # The device as the engine's own mesh reports it — what
                # chip_smoke.py prints, without importing jax itself.
                devs = (list(engine.mesh.devices.flat)
                        if engine is not None else [None])
                body = json.dumps({
                    "ok": ok,
                    "devices": len(devs) if engine is not None else 0,
                    "platform": getattr(devs[0], "platform", None),
                    "device_kind": getattr(devs[0], "device_kind", None),
                }).encode()
                ctype = "application/json"
            elif path == "/models" and method == "GET":
                body = json.dumps(
                    self.registry.models_snapshot(), indent=2
                ).encode()
                status, ctype = "200 OK", "application/json"
            elif path in ("/models/load", "/models/swap", "/models/unload"):
                status, body, ctype = self._admin_models(environ, method, path)
            elif path == "/pipelines" and method == "GET":
                # Pipeline catalog: every registered DAG + its live
                # stage resolution (re-resolved lazily after swaps).
                body = json.dumps(self.pipelines.pipelines_snapshot(),
                                  indent=2).encode()
                status, ctype = "200 OK", "application/json"
            elif path.startswith("/pipelines/") and method == "POST":
                res = self._pipeline_predict(environ,
                                             path[len("/pipelines/"):])
                status, body, ctype = res[0], res[1], res[2]
                if len(res) > 3 and res[3]:
                    extra_headers = list(res[3])
            elif path == "/jobs" or path.startswith("/jobs/"):
                res = self._jobs_route(environ, method, path)
                status, body, ctype = res[0], res[1], res[2]
                if len(res) > 3 and res[3]:
                    extra_headers = list(res[3])
            elif path == "/stats":
                body = json.dumps(self._stats(), indent=2).encode()
                status, ctype = "200 OK", "application/json"
            elif path == "/metrics":
                # Prometheus text exposition — the scrape surface standard
                # monitoring reads without knowing our JSON schema.
                body = self._metrics().encode()
                status, ctype = "200 OK", "text/plain; version=0.0.4"
            elif path == "/debug/slow":
                body = json.dumps(self.obs.flight.snapshot(), indent=2).encode()
                status, ctype = "200 OK", "application/json"
            elif path == "/debug/history":
                # Telemetry rings: bounded history for named series at a
                # chosen resolution — what the autoscaler (and loadgen
                # --history) polls instead of diffing /stats snapshots.
                status, body, ctype = self._history(environ)
            elif path == "/debug/events":
                # Structured event ring: hot-swaps, pressure transitions,
                # chaos injections, parity gates, SLO alert fire/clear.
                status, body, ctype = self._events(environ)
            elif path == "/debug/trace" and method == "POST":
                status, body, ctype = self._trace(environ)
            elif path == "/debug/trace":
                # GET: the exportable timeline — batch lifecycle rings +
                # recent request spans as Chrome-trace/Perfetto JSON. No
                # profiler attached, no traffic interrupted; open the body
                # in chrome://tracing or ui.perfetto.dev.
                status, body, ctype = self._trace_export(environ)
            elif path == "/":
                status, body, ctype = "200 OK", _DEMO_PAGE.encode(), "text/html"
            else:
                status, body, ctype = "404 Not Found", b'{"error": "not found"}', "application/json"
        except socket.timeout:
            # Body read hit the per-request read deadline: client weather
            # (stalled/slow uploader), not a server fault — no traceback.
            log.warning("request read timed out: %s %s", method, path)
            status = "408 Request Timeout"
            body = b'{"error": "request read timed out"}'
            ctype = "application/json"
        except Exception as e:  # request-level failure isolation
            log.exception("request failed: %s %s", method, path)
            status = "500 Internal Server Error"
            body = json.dumps({"error": str(e)}).encode()
            ctype = "application/json"
        if own_span:
            self.obs.finish(span, int(status.split(None, 1)[0]))
        start_response(
            status,
            [
                ("Content-Type", ctype),
                ("Content-Length", str(len(body))),
                ("X-Trace-Id", span.trace_id),
                *extra_headers,
            ],
        )
        return [body]

    def _stats(self) -> dict:
        batcher, engine = self.batcher, self.engine
        if batcher is not None:
            snap = batcher.stats.snapshot()
            snap["queue_depth"] = batcher.queue_depth
            # Live batching window: the adaptive controller's current
            # value, next to the cap it moves under.
            snap["batcher"] = {
                "adaptive_delay_ms": round(
                    getattr(batcher, "current_delay_ms", 0.0), 3
                ),
                "max_delay_ms": batcher.max_delay_s * 1e3,
                "adaptive": getattr(batcher, "adaptive_delay", False),
            }
            if hasattr(batcher, "builder_stats"):
                # Slot-lease assembly: open builders, outstanding leased
                # slots, force-expired leases and padded holes — the
                # host-path occupancy picture next to the device-side
                # occupancy above.
                snap["batcher"]["builders"] = batcher.builder_stats()
            if hasattr(batcher, "lifecycle_stats"):
                # Where a batch's time went (open, launch wait, in flight,
                # and the flight's copy, wait behind earlier calls, device
                # work and copy back), why batches sealed, bytes each way,
                # and how long no batch was launched at all, or only copies
                # flew: cumulative, read as deltas.
                snap["batcher"]["lifecycle"] = batcher.lifecycle_stats()
        else:
            # Default model between versions (drained, or never adopted):
            # the registry block below still tells the whole story.
            snap = {}
        snap["model"] = self.model_cfg.name
        # The registry's view: every model, every version, lifecycle state
        # + transition history + per-model traffic stats.
        snap["models"] = self.registry.models_snapshot()
        if self.http_counters is not None:
            snap["http"] = self.http_counters.snapshot()
        if hasattr(engine, "staging_stats"):
            snap["staging"] = engine.staging_stats()
        if hasattr(engine, "device_memory"):
            snap["device_memory"] = engine.device_memory()
        # Per-stage span aggregates: cumulative count/total_ms per stage
        # (diffable across snapshots — loadgen's stage attribution) plus
        # interpolated p50/p99 from the histogram buckets.
        snap["tracing"] = self.obs.stage_summary()
        # Device economics (serving/costmodel.py): analytic FLOPs/bytes
        # joined with measured per-(replica, canvas, batch-bucket) device
        # time into live MFU / arithmetic-intensity / roofline-bound
        # gauges, plus the batcher's padding-waste fractions — the numbers
        # the bench and profile_serve roofline tables are sourced from.
        snap["economics"] = self._economics()
        # Which image decoder served: the native extension, or PIL (the
        # path a missing compiler or libjpeg silently leaves JPEGs on).
        from .. import native

        snap["decode"] = native.stats()
        # Content-addressed response cache: hit/miss/coalesce counters,
        # live byte/entry gauges, and per-model usage.
        snap["cache"] = self.cache.stats()
        # AOT executable cache: process-wide deserialize-vs-compile
        # counters (monotonic across hot-swaps) plus the default
        # engine's cache location/enabled flag.
        snap["aot_cache"] = aotcache.stats(getattr(engine, "_aot", None))
        # Every backend compile of the process, through the AOT cache or
        # not (a jax.monitoring listener): a jit that compiles while
        # serving shows here and nowhere else.
        snap["compile"] = aotcache.backend_compile_stats()
        # The last POST /debug/trace recording: its interval on the
        # monotonic clock and the batch records that met it (None until
        # one was made).
        snap["profile"] = self._profile
        # Bulk jobs: lifecycle counts, aggregate image counters, recent
        # job documents (progress, versions, resume flags).
        snap["jobs"] = (self.jobs.stats() if self.jobs is not None
                        else {"enabled": False})
        # Overload engineering: per-tenant/per-class admission counters,
        # the degradation ladder's live rung + transition history, and the
        # chaos injector's injection counts (absent unless --chaos).
        overload = {}
        if self.admission is not None:
            overload["admission"] = self.admission.stats()
        if self.pressure is not None:
            overload["pressure"] = self.pressure.stats()
        if self.chaos is not None:
            overload["chaos"] = self.chaos.stats()
        snap["overload"] = overload
        # Pipeline DAGs: per-pipeline request/error counters, windowed
        # e2e percentiles, per-stage seconds/images/cache-hits/D2H, plus
        # costmodel's per-stage econ attribution (which stage to
        # quantize/re-place next).
        ps = self.pipelines.pipeline_stats()
        for pstat in ps["pipelines"].values():
            try:
                pstat["attribution"] = costmodel.pipeline_attribution(
                    pstat, self.registry)
            except Exception:  # attribution must never fail /stats
                log.exception("pipeline attribution failed")
        snap["pipelines"] = ps
        # Telemetry history: ring memory + series count + sampler health
        # + SLO burn-rate alert state + event-ring usage.
        snap["telemetry"] = (self.telemetry.stats()
                             if self.telemetry is not None
                             else {"enabled": False})
        # Live serving config: the knobs that explain the numbers
        # above (an operator reading p99 needs to know the wire
        # format and buckets without ssh-ing for the start command).
        snap["config"] = self._config_echo
        return snap

    def _economics(self) -> dict:
        """Per serving-version economics: costmodel's roofline attribution
        over the engine's measured device-time counters, plus the
        batcher's padding-waste block. Versions on engines without econ
        counters (mocks, embedders) are simply absent."""
        out = {}
        for mv in self.registry.serving_entries():
            try:
                econ = costmodel.economics_snapshot(mv.engine, mv.model_cfg)
            except Exception:  # economics must never fail /stats
                log.exception("economics snapshot failed for %s", mv.ref)
                econ = None
            pad = None
            if hasattr(mv.batcher, "builder_stats"):
                pad = mv.batcher.builder_stats().get("padding") or None
            if econ is None and pad is None:
                continue
            entry = econ if econ is not None else {}
            if pad is not None:
                entry["padding"] = pad
            out[f"{mv.name}@{mv.version}"] = entry
        return out

    def _metrics(self) -> str:
        """Render every counter/gauge/histogram as Prometheus text. The
        span-derived block comes from ONE Observability snapshot, so the
        e2e histogram's +Inf count always equals requests_total summed over
        status classes — the consistency the smoke test asserts."""
        p = PromText()
        # Resolve the default model's live handles ONCE: the properties
        # re-resolve through the registry, and a swap draining the default
        # version mid-render (registry nulls mv.batcher/engine) must not
        # turn the None-check and the dereference into a TOCTOU 500.
        batcher, engine = self.batcher, self.engine
        peak_done: set = set()  # backend peak gauges emitted once per scrape
        obs = self.obs.snapshot()
        p.scalar("uptime_seconds", obs["uptime_s"],
                 help_="Seconds since this app started (monotonic).")
        for klass in sorted(obs["requests_by_status"]):
            p.scalar("requests_total", obs["requests_by_status"][klass],
                     mtype="counter", labels={"status": klass},
                     help_="Finished HTTP requests by status class.")
        p.histogram("request_duration_seconds", obs["e2e"],
                    help_="End-to-end request latency (span total).")
        for stage in sorted(obs["stages"]):
            p.histogram("stage_duration_seconds", obs["stages"][stage],
                        labels={"stage": stage},
                        help_="Per-stage request latency (span stages).")
        if batcher is not None:
            snap = batcher.stats.snapshot()
            p.scalar("inferences_total", snap["requests_total"], mtype="counter",
                     help_="Images through the batcher (incl. errors).")
            p.scalar("inference_errors_total", snap["errors_total"],
                     mtype="counter", help_="Failed batcher requests.")
            p.scalar("batches_dispatched_total",
                     snap.get("batches_dispatched_total", 0), mtype="counter",
                     help_="Device batches dispatched.")
            if snap.get("batch_occupancy") is not None:
                p.scalar("batch_occupancy", snap["batch_occupancy"],
                         help_="Real rows / bucket rows, rolling window.")
            p.scalar("queue_depth", batcher.queue_depth,
                     help_="Leased-but-undispatched batch slots (assembly backlog).")
            p.scalar("batch_delay_seconds",
                     getattr(batcher, "current_delay_ms", 0.0) / 1e3,
                     help_="Live adaptive batch-assembly window.")
            if hasattr(batcher, "builder_stats"):
                bs = batcher.builder_stats()
                p.scalar("builders_open", bs["open_builders"],
                         help_="Batch builders assembling (open + sealing).")
                p.scalar("batches_sealed_total", bs["batches_sealed_total"],
                         mtype="counter", help_="Batch builders sealed and "
                         "dispatched or discarded.")
                p.scalar("lease_timeouts_total", bs["lease_timeouts_total"],
                         mtype="counter",
                         help_="Slot leases force-expired (lessee died or "
                         "exceeded the lease timeout).")
                p.scalar("batch_holes_total", bs["holes_total"], mtype="counter",
                         help_="Batch slots dispatched as hw=1x1 padding "
                         "(released, failed, or expired leases).")
                p.scalar("pipeline_depth", bs["pipeline_depth"],
                         help_="Configured batches in flight per canvas "
                         "bucket (sealed->launched->unfetched).")
                p.scalar("pipeline_inflight_batches", bs["inflight_batches"],
                         help_="Batches currently in flight on the device "
                         "pipeline (launched, outputs not yet fetched).")
                p.scalar("backlog_rejections_total",
                         bs["backlog_rejections_total"], mtype="counter",
                         help_="Requests fast-rejected with 503 because the "
                         "batcher backlog hit max_queue.")
                p.scalar("deadline_sheds_total",
                         bs.get("deadline_sheds_total", 0), mtype="counter",
                         help_="Requests shed at admission because the "
                         "expected wait exceeded their deadline.")
                p.scalar("deadline_seal_sheds_total",
                         bs.get("deadline_seal_sheds_total", 0),
                         mtype="counter",
                         help_="Leases shed at batch seal: the deadline "
                         "passed while the slot waited for dispatch.")
                p.scalar("quota_sheds_total",
                         bs.get("quota_sheds_total", 0), mtype="counter",
                         help_="Requests shed by per-tenant token-bucket "
                         "quota (answered 429).")
        # Per-tenant / per-SLO-class admission counters (cardinality is
        # capped by the controller: unknown tenants past --tenant-max-
        # tracked collapse into the "~other" bucket).
        if self.admission is not None:
            a = self.admission.stats()
            for tname, t in a["tenants"].items():
                p.scalar("tenant_admitted_total", t["admitted"],
                         mtype="counter", labels={"tenant": tname},
                         help_="Requests admitted, by tenant.")
                for reason in sorted(t["shed"]):
                    p.scalar("tenant_shed_total", t["shed"][reason],
                             mtype="counter",
                             labels={"tenant": tname, "reason": reason},
                             help_="Requests shed, by tenant and reason.")
            for cname, c in a["classes"].items():
                p.scalar("slo_class_admitted_total", c["admitted"],
                         mtype="counter", labels={"slo_class": cname},
                         help_="Requests admitted, by SLO class.")
                for reason in sorted(c["shed"]):
                    p.scalar("slo_class_shed_total", c["shed"][reason],
                             mtype="counter",
                             labels={"slo_class": cname, "reason": reason},
                             help_="Requests shed, by SLO class and reason.")
        if self.pressure is not None:
            pr = self.pressure.stats()
            p.scalar("pressure_level", pr["level"],
                     help_="Degradation-ladder rung (0 = normal service).")
            p.scalar("pressure_transitions_total", pr["transitions_total"],
                     mtype="counter",
                     help_="Degradation-ladder rung transitions.")
        if self.chaos is not None:
            ch = self.chaos.stats()
            for k in ("decode_failures_injected", "dispatch_failures_injected",
                      "slow_fetches_injected", "spike_holds_injected"):
                p.scalar(f"chaos_{k}_total", ch[k], mtype="counter",
                         help_="Chaos-injector fault injections.")
        if self.http_counters is not None:
            h = self.http_counters.snapshot()
            p.scalar("http_connections_total", h["connections_total"],
                     mtype="counter", help_="TCP connections accepted.")
            p.scalar("http_requests_total", h["requests_total"], mtype="counter",
                     help_="HTTP requests served (all routes).")
            p.scalar("http_active_connections", h["active_connections"],
                     help_="Currently open connections.")
        if hasattr(engine, "staging_stats"):
            s = engine.staging_stats()
            p.scalar("staging_slab_acquires_total", s["slab_acquires_total"],
                     mtype="counter", help_="Lifetime staging-slab acquisitions "
                     "(reuse share = 1 - allocs / acquires).")
            p.scalar("staging_slab_allocs_total", s["slab_allocs_total"],
                     mtype="counter", help_="Lifetime staging-slab allocations.")
            p.scalar("staging_slabs_out", s["slabs_out"],
                     help_="Staging slabs out with batches (acquired, not "
                     "yet returned).")
            p.scalar("staging_out_bytes", s["slabs_out_bytes"],
                     help_="Host bytes of the staging slabs that are out; the "
                     "idle pool may hold staging_pool_bytes plus this.")
            p.scalar("staging_slabs_pooled", s["slabs_pooled"],
                     help_="Idle staging slabs in the pool.")
            p.scalar("staging_pooled_bytes", s["slabs_pooled_bytes"],
                     help_="Host bytes held by idle staging slabs.")
        # Per-model registry block: lifecycle state per version (Prometheus
        # enum pattern: the current state's sample is 1) and per-model
        # traffic counters from each serving version's own batcher — the
        # unlabeled aggregates above stay as the default model's for
        # dashboard back-compat.
        reg = self.registry.models_snapshot(include_stats=False)
        for name, info in reg["models"].items():
            for v in info["versions"]:
                p.scalar(
                    "model_state", 1,
                    labels={"model": name, "version": v["version"],
                            "state": v["state"]},
                    help_="Lifecycle state per model version (enum: the "
                          "current state's sample is 1).",
                )
        p.scalar("model_swaps_total", reg["swaps_total"], mtype="counter",
                 help_="Hot-swap requests accepted by the registry.")
        p.scalar("model_loads_failed_total", reg["loads_failed_total"],
                 mtype="counter",
                 help_="Model loads that FAILED (build or warmup).")
        for mv in self.registry.serving_entries():
            stats = getattr(mv.batcher, "stats", None)
            if stats is None:
                continue
            ms = stats.snapshot()
            labels = {"model": mv.name, "version": mv.version}
            p.scalar("model_inferences_total", ms["requests_total"],
                     mtype="counter", labels=labels,
                     help_="Images through this model's batcher (incl. errors).")
            p.scalar("model_inference_errors_total", ms["errors_total"],
                     mtype="counter", labels=labels,
                     help_="Failed requests on this model's batcher.")
            p.scalar("model_latency_p50_seconds",
                     ms["latency_ms"]["p50"] / 1e3, labels=labels,
                     help_="Rolling p50 latency through this model's batcher.")
            p.scalar("model_queue_depth",
                     getattr(mv.batcher, "queue_depth", 0), labels=labels,
                     help_="This model's leased-but-undispatched slots.")
            if hasattr(mv.batcher, "builder_stats"):
                mbs = mv.batcher.builder_stats()
                p.scalar("model_backlog_rejections_total",
                         mbs["backlog_rejections_total"], mtype="counter",
                         labels=labels,
                         help_="503 fast-rejects on this model's bounded "
                         "queue (admission precedes placement routing, so "
                         "rejections are per model, not per replica).")
                p.scalar("model_pipeline_inflight_batches",
                         mbs["inflight_batches"], labels=labels,
                         help_="This model's batches in flight on the "
                         "device pipeline.")
            p.scalar("model_inflight_requests", mv.inflight, labels=labels,
                     help_="HTTP requests currently holding this version.")
            # Per-replica placement attribution: in-flight dispatches, slab
            # bytes on the wire/device, and cumulative device-phase busy
            # seconds per {model, version, replica} — rate(busy_seconds)
            # over wall clock is each chip group's busy fraction, the
            # number loadgen's stage-utilization table renders per chip.
            est = getattr(mv.engine, "staging_stats", None)
            for rep in (est().get("replicas", []) if est else []):
                rl = dict(labels, replica=rep["replica"])
                p.scalar("model_replica_dispatches_total",
                         rep["dispatches_total"], mtype="counter", labels=rl,
                         help_="Batches dispatched to this placement "
                         "replica.")
                p.scalar("model_replica_dispatches_inflight",
                         rep["dispatches_inflight"], labels=rl,
                         help_="Batches in flight on this placement "
                         "replica (dispatched, outputs not yet fetched).")
                p.scalar("model_replica_slab_bytes_inflight",
                         rep["slab_bytes_inflight"], labels=rl,
                         help_="Staging-slab bytes owned by this replica's "
                         "in-flight batches (slab occupancy per replica).")
                p.scalar("model_replica_busy_seconds_total",
                         rep["busy_s"], mtype="counter", labels=rl,
                         help_="Cumulative device-phase seconds on this "
                         "replica: each call from the device's turn to "
                         "its outputs computed.")
            if hasattr(mv.batcher, "lifecycle_stats"):
                _lifecycle_metrics(p, mv.batcher.lifecycle_stats(), labels)
            self._econ_metrics(p, mv, peak_done)
        # Content-addressed response cache: aggregate counters/gauges plus
        # per-model usage labels — the observability half of the tentpole
        # (hit-rate and coalesce counts are what the bench's goodput
        # multiplier is made of).
        c = self.cache.stats()
        p.scalar("cache_hits_total", c["hits_total"], mtype="counter",
                 help_="Requests served from the response cache.")
        p.scalar("cache_misses_total", c["misses_total"], mtype="counter",
                 help_="Cache lookups that led a fresh computation.")
        p.scalar("cache_coalesced_total", c["coalesced_total"],
                 mtype="counter",
                 help_="Requests coalesced onto another request's "
                 "in-flight computation (single-flight dedup).")
        p.scalar("cache_evictions_total", c["evictions_total"],
                 mtype="counter",
                 help_="Entries evicted by the LRU byte budget.")
        p.scalar("cache_invalidations_total", c["invalidations_total"],
                 mtype="counter",
                 help_="Entries dropped by model retire (hot-swap/unload).")
        p.scalar("cache_digest_bytes_total", c["digest_bytes_total"],
                 mtype="counter",
                 help_="Bytes hashed for the keys of cache lookups (an "
                 "image is keyed by its upload's bytes).")
        p.scalar("cache_bytes", c["bytes"],
                 help_="Bytes held by cached responses (budget: "
                 "--cache-bytes; 0 = cache disabled).")
        p.scalar("cache_entries", c["entries"],
                 help_="Live cached responses.")
        p.scalar("cache_inflight", c["inflight"],
                 help_="Single-flight computations currently in flight.")
        # AOT executable cache: the deserialize-instead-of-compile
        # counters behind the cold-start numbers (process-wide, so they
        # never reset across hot-swaps).
        a = aotcache.stats()
        p.scalar("aot_cache_hits_total", a["hits_total"], mtype="counter",
                 help_="Executables deserialized from the AOT cache "
                 "instead of compiled.")
        p.scalar("aot_cache_misses_total", a["misses_total"],
                 mtype="counter",
                 help_="AOT cache lookups that fell through to a compile.")
        p.scalar("aot_cache_writes_total", a["writes_total"],
                 mtype="counter",
                 help_="Freshly compiled executables persisted to the "
                 "AOT cache.")
        p.scalar("aot_cache_corrupt_total", a["corrupt_total"],
                 mtype="counter",
                 help_="AOT cache entries rejected as unusable (bad "
                 "magic/checksum, key mismatch, deserialize failure); "
                 "each fell back to a recompile.")
        p.scalar("aot_cache_bytes_total", a["bytes_written_total"],
                 mtype="counter",
                 help_="Bytes of serialized executables written to the "
                 "AOT cache.")
        for name, mc in c["per_model"].items():
            ml = {"model": name}
            p.scalar("model_cache_hits_total", mc["hits"], mtype="counter",
                     labels=ml, help_="Cache hits for this model.")
            p.scalar("model_cache_misses_total", mc["misses"],
                     mtype="counter", labels=ml,
                     help_="Cache misses for this model.")
            p.scalar("model_cache_coalesced_total", mc["coalesced"],
                     mtype="counter", labels=ml,
                     help_="Coalesced (single-flight) waits for this model.")
            p.scalar("model_cache_bytes", mc["bytes"], labels=ml,
                     help_="Bytes of this model's cached responses.")
        # Bulk jobs: lifecycle gauge per state + aggregate image counters
        # (tpu_serve_job_*) — the observability half of the /jobs tentpole.
        if self.jobs is not None:
            js = self.jobs.stats()
            for state in ("QUEUED", "RUNNING", "PAUSED", "DONE", "FAILED",
                          "CANCELLED"):
                p.scalar("jobs", js["by_state"].get(state, 0),
                         labels={"state": state},
                         help_="Bulk jobs by lifecycle state.")
            p.scalar("job_images_done_total", js["images_done_total"],
                     mtype="counter",
                     help_="Images completed (spooled) across all jobs.")
            p.scalar("job_images_cached_total", js["images_cached_total"],
                     mtype="counter",
                     help_="Job images served from (or coalesced onto) the "
                     "response cache instead of a bulk dispatch.")
            p.scalar("job_image_errors_total", js["image_errors_total"],
                     mtype="counter",
                     help_="Job images that ended as error lines "
                     "(undecodable, unreadable, retries exhausted).")
            p.scalar("job_chunks_total", js["chunks_total"], mtype="counter",
                     help_="Completed-and-checkpointed job chunks.")
            bcache = c.get("bulk", {})
            p.scalar("job_cache_hits_total", bcache.get("hits_total", 0),
                     mtype="counter",
                     help_="Bulk-tier response-cache hits (job lookups are "
                     "counted apart from the interactive tier).")
        self._pipeline_metrics(p)
        if self.telemetry is not None:
            self._telemetry_metrics(p)
        return p.render()

    def _pipeline_metrics(self, p: PromText) -> None:
        """Pipeline-DAG families (tpu_serve_pipeline_*): per-pipeline
        traffic/error counters and windowed e2e percentiles, per-stage
        device seconds / images / cache hits / D2H bytes, and the
        catalog's swap-driven re-resolution counter. Per-stage span
        latency already rides stage_duration_seconds{stage=
        "pipeline.<model>"} — no extra family needed."""
        ps = self.pipelines.pipeline_stats()
        p.scalar("pipeline_resolutions_total", ps["resolutions_total"],
                 mtype="counter",
                 help_="Pipeline re-resolutions triggered by stage-model "
                 "serving/retire transitions.")
        for name in sorted(ps["pipelines"]):
            st = ps["pipelines"][name]
            pl = {"pipeline": name}
            p.scalar("pipeline_requests_total", st["requests_total"],
                     mtype="counter", labels=pl,
                     help_="Pipeline executions (all outcomes).")
            p.scalar("pipeline_errors_total", st["errors_total"],
                     mtype="counter", labels=pl,
                     help_="Pipeline executions that raised.")
            for q, key in (("p50", "e2e_p50_s"), ("p99", "e2e_p99_s")):
                if st[key] is not None:
                    p.scalar(f"pipeline_e2e_{q}_seconds", st[key],
                             labels=pl,
                             help_="Windowed pipeline end-to-end latency "
                             "(last 512 requests).")
            for stage in sorted(st["stages"]):
                sl = {"pipeline": name, "stage": stage}
                sc = st["stages"][stage]
                p.scalar("pipeline_stage_seconds_total", sc["seconds"],
                         mtype="counter", labels=sl,
                         help_="Wall seconds attributed to this stage "
                         "(dispatch through result).")
                p.scalar("pipeline_stage_images_total", sc["images"],
                         mtype="counter", labels=sl,
                         help_="Images (stage 1) or crops (later stages) "
                         "through this stage.")
                p.scalar("pipeline_stage_cache_hits_total",
                         sc["cache_hits"], mtype="counter", labels=sl,
                         help_="Per-stage response-cache hits.")
                p.scalar("pipeline_stage_d2h_bytes_total",
                         sc["d2h_bytes"], mtype="counter", labels=sl,
                         help_="Device-to-host bytes this stage actually "
                         "converted (payload rows, not padded buckets).")

    def _telemetry_metrics(self, p: PromText) -> None:
        """Telemetry-subsystem health + SLO burn-rate exposition: ring
        memory, sampler ticks/overruns, and one burn-rate gauge per
        (objective, window) with the machine-readable alert state."""
        ts = self.telemetry.stats()
        p.scalar("telemetry_memory_bytes", ts["memory_bytes"],
                 help_="Live bytes held by the telemetry history rings "
                 "(fixed arrays; bounded by series cap x resolutions).")
        p.scalar("telemetry_series", ts["series_count"],
                 help_="Named series currently held by the telemetry "
                 "rings.")
        p.scalar("telemetry_samples_total", ts["samples_total"],
                 mtype="counter",
                 help_="Completed telemetry sampler ticks.")
        p.scalar("telemetry_overruns_total", ts["overruns_total"],
                 mtype="counter",
                 help_="Sampler ticks that took longer than the sample "
                 "interval (collection is falling behind).")
        for name, al in sorted(ts["slo"].items()):
            for window, burn in sorted(al["burn"].items()):
                p.scalar("slo_burn_rate", burn,
                         labels={"class": name, "window": window},
                         help_="SLO error-budget burn rate per objective "
                         "and window (1.0 = burning exactly the budget; "
                         "the fast pair pages at 14.4, the slow window "
                         "at 6).")
            p.scalar("slo_alert_firing", al["state"] == "firing",
                     labels={"class": name},
                     help_="1 while the objective's multi-window burn-rate "
                     "alert is firing, else 0.")

    def _econ_metrics(self, p: PromText, mv, peak_done: set) -> None:
        """Device-economics exposition for one serving version: live MFU /
        achieved-FLOP/s / arithmetic-intensity / roofline-bound gauges per
        (replica, canvas, batch-bucket) cell, device-time and row counters
        per cell, and the batcher's padding-waste counters per bucket.
        "compute-bound at 0.058 of peak" as a scraped gauge, not a
        BASELINE sentence."""
        if not hasattr(mv.engine, "econ_stats"):
            return
        try:
            econ = costmodel.economics_snapshot(mv.engine, mv.model_cfg)
        except Exception:  # economics must never fail a scrape
            log.exception("economics metrics failed for %s", mv.ref)
            return
        if not econ:
            return
        # dtype label: the same network served at f32/bf16/int8 is three
        # different roofline positions — dashboards must never average
        # tiers into one line.
        base = {"model": mv.name, "version": mv.version,
                "dtype": econ.get("dtype",
                                  getattr(mv.model_cfg, "dtype", "bfloat16"))}
        if "mfu" in econ:
            p.scalar("model_mfu", econ["mfu"], labels=base,
                     help_="Whole-placement model FLOP utilization: useful "
                     "FLOP/s over measured device-busy time, vs the "
                     "backend peak (TPU: spec table; CPU mesh: calibrated "
                     "once).")
        p.scalar("model_padded_rows_fraction", econ["padded_rows_fraction"],
                 labels=base,
                 help_="Lifetime fraction of dispatched batch rows that "
                 "carried no request (batch padding up to compiled "
                 "buckets).")
        for rep in econ["replicas"]:
            for cell in rep["buckets"]:
                cl = dict(base, replica=rep["replica"],
                          canvas=cell["canvas"],
                          bucket=cell["batch_bucket"])
                p.scalar("model_econ_device_seconds_total",
                         cell["device_s"], mtype="counter", labels=cl,
                         help_="Measured dispatch-to-fetch device seconds "
                         "per (replica, canvas, batch bucket) cell.")
                p.scalar("model_econ_rows_total", cell["rows"],
                         mtype="counter", labels=cl,
                         help_="Rows staged (requests + holes) per "
                         "economics cell.")
                p.scalar("model_econ_rows_dispatched_total",
                         cell["rows_dispatched"], mtype="counter",
                         labels=cl,
                         help_="Rows the compiled bucket shape dispatched "
                         "per economics cell (incl. padding).")
                if cell.get("achieved_flops") is None:
                    continue
                p.scalar("model_achieved_flops", cell["achieved_flops"],
                         labels=cl,
                         help_="Useful FLOP/s achieved in this cell "
                         "(analytic per-image FLOPs x rows / device "
                         "seconds).")
                p.scalar("model_cell_mfu", cell["mfu"], labels=cl,
                         help_="This cell's useful FLOP/s over the "
                         "replica's peak.")
                p.scalar("model_arithmetic_intensity",
                         cell["arithmetic_intensity"], labels=cl,
                         help_="Analytic FLOPs per HBM byte at this "
                         "(canvas, batch) operating point.")
                if cell.get("roofline_bound_fraction") is not None:
                    p.scalar("model_roofline_bound_fraction",
                             cell["roofline_bound_fraction"], labels=cl,
                             help_="Achieved FLOP/s over the BINDING "
                             "roofline ceiling (compute peak or "
                             "AI x bandwidth, whichever is lower).")
        # Padding counters come from the BATCHER (economics_snapshot is
        # engine-side and never carries them; App._economics merges the
        # two only for the /stats document).
        pad = None
        if hasattr(mv.batcher, "builder_stats"):
            pad = mv.batcher.builder_stats().get("padding")
        for cell in (pad or {}).values():
            cl = dict(base, canvas=cell["canvas"],
                      bucket=cell["batch_bucket"])
            p.scalar("model_padding_rows_real_total", cell["rows_real"],
                     mtype="counter", labels=cl,
                     help_="Dispatched rows that carried a committed "
                     "request, per (canvas, batch bucket).")
            p.scalar("model_padding_rows_dispatched_total",
                     cell["rows_dispatched"], mtype="counter", labels=cl,
                     help_="Rows dispatched at the compiled bucket shape, "
                     "per (canvas, batch bucket).")
            p.scalar("model_padding_px_real_total", cell["px_real"],
                     mtype="counter", labels=cl,
                     help_="Real image pixels shipped, per (canvas, batch "
                     "bucket) — vs the padded canvas pixels below.")
            p.scalar("model_padding_px_dispatched_total",
                     cell["px_dispatched"], mtype="counter", labels=cl,
                     help_="Canvas pixels shipped (incl. padding), per "
                     "(canvas, batch bucket).")
        peak = econ.get("peak")
        # The peak is backend-global PER SERVING DTYPE (f32 halves the
        # TPU compute peak; int8 shares bf16's): emit each dtype's pair
        # once per scrape, labeled — duplicate samples of one series
        # would fail any strict exposition parser.
        dtype = base["dtype"]
        if peak and ("peak", dtype) not in peak_done:
            peak_done.add(("peak", dtype))
            dl = {"dtype": dtype}
            p.scalar("device_peak_flops_per_chip", peak["flops_per_chip"],
                     labels=dl,
                     help_="Per-chip peak FLOP/s the MFU gauges divide by "
                     "at this serving dtype (TPU: spec table, f32 at half "
                     "the bf16 rate, int8 at it; CPU: calibrated once per "
                     "compute dtype).")
            p.scalar("device_peak_hbm_bytes_per_s_per_chip",
                     peak["hbm_bytes_per_s_per_chip"], labels=dl,
                     help_="Per-chip peak memory bandwidth for the "
                     "roofline ridge point.")

    def _admin_models(self, environ, method: str, path: str):
        """POST /models/{load,swap,unload}: JSON body in, the affected
        version's (name, version, state) out. Loads/swaps run on the
        registry's loader thread; ``"wait": true`` blocks the response
        until the version reaches a terminal state (handy for scripts and
        the hot-swap tests; watchers poll GET /models instead)."""
        if method != "POST":
            return ("405 Method Not Allowed",
                    b'{"error": "POST required"}', "application/json")
        body = self._read_body(environ)
        if body is None:
            return ("413 Content Too Large",
                    b'{"error": "body too large"}', "application/json")
        try:
            d = json.loads(body or b"{}")
            if not isinstance(d, dict):
                raise ValueError("body must be a JSON object")
        except ValueError as e:
            return ("400 Bad Request",
                    json.dumps({"error": f"bad JSON body: {e}"}).encode(),
                    "application/json")
        wait = bool(d.get("wait", False))
        try:
            # Inside the mapping try: a malformed timeout_s is a bad
            # request (400 below), not a 500.
            timeout = float(d.get("timeout_s", 600.0))
            if path == "/models/load":
                spec = d.get("model")
                if not spec:
                    return ("400 Bad Request",
                            b'{"error": "\'model\' (preset name, native:<zoo>, '
                            b'.pb/.json path) is required"}',
                            "application/json")
                mv = self.registry.load(
                    spec, name=d.get("name"),
                    activate=bool(d.get("activate", True)),
                    wait=wait, timeout=timeout,
                )
            elif path == "/models/swap":
                mv = self.registry.swap(
                    d.get("name"), d.get("model"), wait=wait, timeout=timeout
                )
            else:  # /models/unload
                name = d.get("name")
                if not name:
                    return ("400 Bad Request",
                            b'{"error": "\'name\' is required"}',
                            "application/json")
                version = d.get("version")
                mv = self.registry.unload(
                    name, int(version) if version is not None else None,
                    wait=wait, timeout=timeout,
                )
        except UnknownModel as e:
            return ("404 Not Found",
                    json.dumps({"error": str(e.args[0] if e.args else e)}).encode(),
                    "application/json")
        except ModelNotServing as e:
            # The model exists but is in the wrong lifecycle state for this
            # admin action — a state conflict, not a routing failure.
            return ("409 Conflict", json.dumps({"error": str(e)}).encode(),
                    "application/json")
        except RuntimeError as e:
            # "registry is stopped": the process is draining — the standard
            # 503 retry-elsewhere signal, same as ShuttingDown on /predict.
            # (ModelNotServing subclasses RuntimeError; its clause above
            # catches first.)
            return ("503 Service Unavailable",
                    json.dumps({"error": str(e)}).encode(), "application/json")
        except TimeoutError as e:
            return ("504 Gateway Timeout",
                    json.dumps({"error": str(e)}).encode(), "application/json")
        except (TypeError, ValueError, OSError) as e:
            # OSError covers spec resolution on a missing/unreadable
            # .pb/.json path — a bad request, not a server fault.
            return ("400 Bad Request",
                    json.dumps({"error": f"{type(e).__name__}: {e}"}).encode(),
                    "application/json")
        resp = {"name": mv.name, "version": mv.version, "state": mv.state}
        if mv.error:
            resp["error"] = mv.error
        if mv.state == FAILED:
            status = "500 Internal Server Error"
        elif wait:
            status = "200 OK"
        else:
            status = "202 Accepted"  # the loader thread is on it; poll /models
        return status, json.dumps(resp).encode(), "application/json"

    # ----------------------------------------------------------------- jobs

    def _jobs_route(self, environ, method: str, path: str):
        """Dispatch /jobs, /jobs/{id}, /jobs/{id}/results,
        /jobs/{id}/cancel. Same trust model as the admin /models routes."""
        if self.jobs is None:
            return ("503 Service Unavailable",
                    b'{"error": "bulk jobs disabled; start the server with '
                    b'--jobs-dir"}', "application/json")
        parts = [p for p in path.split("/") if p]  # ["jobs", id?, verb?]
        try:
            if len(parts) == 1:
                if method == "POST":
                    return self._jobs_submit(environ)
                if method == "GET":
                    body = json.dumps({"jobs": self.jobs.list_jobs()},
                                      indent=2).encode()
                    return "200 OK", body, "application/json"
                return ("405 Method Not Allowed",
                        b'{"error": "GET or POST"}', "application/json")
            job_id = parts[1]
            if len(parts) == 2 and method == "GET":
                body = json.dumps(self.jobs.get_job(job_id), indent=2).encode()
                return "200 OK", body, "application/json"
            if len(parts) == 3 and parts[2] == "results" and method == "GET":
                return self._jobs_results(environ, job_id)
            if len(parts) == 3 and parts[2] == "cancel" and method == "POST":
                body = json.dumps(self.jobs.cancel_job(job_id),
                                  indent=2).encode()
                return "200 OK", body, "application/json"
        except UnknownJob as e:
            return ("404 Not Found",
                    json.dumps({"error": str(e.args[0] if e.args else e)}).encode(),
                    "application/json")
        return ("404 Not Found", b'{"error": "not found"}',
                "application/json")

    def _jobs_submit(self, environ):
        """POST /jobs: multipart upload (file parts = the manifest) or a
        JSON body naming a server-side directory. 202 + the job document —
        the runner proceeds in the background; poll GET /jobs/{id}."""
        qs = urllib.parse.parse_qs(
            environ.get("QUERY_STRING", ""), keep_blank_values=True
        )
        model = _qs_last(qs, "model")
        try:
            topk_raw = _qs_last(qs, "topk")
            topk = int(topk_raw) if topk_raw is not None else None
        except ValueError:
            return ("400 Bad Request", b'{"error": "topk must be an integer"}',
                    "application/json")
        # Tenant + job-vs-job weight: the tenant keys the bulk quota gate
        # (this job's batches count against X-Tenant's token bucket), the
        # weight orders the single-runner queue (higher runs first).
        tenant = ((environ.get("HTTP_X_TENANT") or "").strip()[:64]
                  or DEFAULT_TENANT)
        try:
            weight = float(_qs_last(qs, "weight") or 1.0)
        except ValueError:
            return ("400 Bad Request", b'{"error": "weight must be a number"}',
                    "application/json")
        body = self._read_body(environ)
        if body is None:
            return ("413 Content Too Large",
                    json.dumps({"error": f"body exceeds "
                                f"{self.cfg.max_body_mb} MB cap"}).encode(),
                    "application/json")
        ctype_in = environ.get("CONTENT_TYPE", "")
        try:
            if ctype_in.startswith("multipart/form-data"):
                files = _parse_multipart_files(body, ctype_in)
                if not files:
                    return ("400 Bad Request",
                            b'{"error": "no file parts in multipart body"}',
                            "application/json")
                job = self.jobs.submit_upload(files, model, topk,
                                              tenant=tenant, weight=weight)
            else:
                try:
                    d = json.loads(body or b"{}")
                    if not isinstance(d, dict):
                        raise ValueError("body must be a JSON object")
                except ValueError as e:
                    return ("400 Bad Request",
                            json.dumps({"error": f"bad JSON body: {e}"}).encode(),
                            "application/json")
                src = d.get("dir")
                if not src:
                    return ("400 Bad Request",
                            b'{"error": "send a multipart upload or a JSON '
                            b'body with \'dir\' (server-side path)"}',
                            "application/json")
                # Same syntax gate the query-string topk gets above: a bad
                # value must 400 here, not FAIL the job at its first chunk.
                try:
                    body_topk = d.get("topk", topk)
                    body_topk = (int(body_topk)
                                 if body_topk is not None else None)
                except (TypeError, ValueError):
                    return ("400 Bad Request",
                            b'{"error": "topk must be an integer"}',
                            "application/json")
                try:
                    body_weight = float(d.get("weight", weight))
                except (TypeError, ValueError):
                    return ("400 Bad Request",
                            b'{"error": "weight must be a number"}',
                            "application/json")
                job = self.jobs.submit_dir(
                    str(src), d.get("model", model), body_topk,
                    glob=str(d.get("glob", "*")),
                    recursive=bool(d.get("recursive", False)),
                    tenant=str(d.get("tenant", tenant))[:64] or tenant,
                    weight=body_weight,
                )
        except UnknownModel as e:
            return ("404 Not Found",
                    json.dumps({"error": str(e.args[0] if e.args else e)}).encode(),
                    "application/json")
        except ValueError as e:
            return ("400 Bad Request", json.dumps({"error": str(e)}).encode(),
                    "application/json")
        doc = job.snapshot()
        doc["results_url"] = f"/jobs/{job.id}/results"
        return "202 Accepted", json.dumps(doc, indent=2).encode(), "application/json"

    def _jobs_results(self, environ, job_id: str):
        """GET /jobs/{id}/results: JSON lines from ``offset``, with the
        resume cursor and live state in headers — the offset-based
        incremental stream (re-poll with X-Job-Next-Offset until
        X-Job-Complete: 1)."""
        qs = urllib.parse.parse_qs(
            environ.get("QUERY_STRING", ""), keep_blank_values=True
        )
        try:
            offset = int(_qs_last(qs, "offset") or 0)
            limit = min(int(_qs_last(qs, "limit") or 10_000), 100_000)
            wait_s = min(float(_qs_last(qs, "wait_s") or 0.0), 30.0)
        except ValueError:
            return ("400 Bad Request",
                    b'{"error": "offset/limit must be integers, wait_s a '
                    b'number"}', "application/json")
        lines, next_offset, state, total_lines = self.jobs.read_results(
            job_id, offset=offset, limit=limit, wait_s=wait_s
        )
        body = b"\n".join(lines) + (b"\n" if lines else b"")
        done = state in ("DONE", "FAILED", "CANCELLED") and next_offset >= total_lines
        headers = [
            ("X-Job-State", state),
            ("X-Job-Next-Offset", str(next_offset)),
            ("X-Job-Result-Lines", str(total_lines)),
            ("X-Job-Complete", "1" if done else "0"),
        ]
        return "200 OK", body, "application/x-ndjson", headers

    # --------------------------------------------------------------- routes

    def _read_body(self, environ) -> bytes | None:
        """Read the request body; ``None`` means it exceeds the size cap.

        The declared Content-Length gates BEFORE any buffering, and the
        read itself is capped too, so a client that under-declares cannot
        stream gigabytes into RAM either.
        """
        cap = int(self.cfg.max_body_mb * 1e6)
        try:
            length = int(environ.get("CONTENT_LENGTH") or 0)
        except ValueError:
            length = -1
        if length < 0 or length > cap:
            # Negative/garbage declared lengths are refused outright: read(-1)
            # would buffer the whole stream, defeating the cap.
            return None
        body = environ["wsgi.input"].read(min(length, cap + 1)) if length else b""
        return None if len(body) > cap else body

    def _predict(self, environ):
        t0 = time.monotonic()
        # twdlint: disable=pairing(on the server path the span comes from environ and is finished by its owner — __call__ or the pooled handler; the fresh-Span fallback exists only for direct _predict callers in tests, whose spans are deliberately unaggregated)
        span = environ.get("tpu_serve.span") or Span()
        # parse_qs, not a hand-rolled split: percent-encoded values must
        # decode, and duplicate keys must not shadow each other silently.
        qs = urllib.parse.parse_qs(
            environ.get("QUERY_STRING", ""), keep_blank_values=True
        )
        spec = _qs_last(qs, "model")
        # Overload context: tenant key, SLO class, and the client's
        # deadline budget. Parsed BEFORE the body read so a malformed
        # deadline 400s without buffering the upload. The deadline anchors
        # at t0 (request receipt): the client's budget includes the upload
        # time, unlike the operator's request_timeout_s which anchors
        # after the body read.
        tenant = ((environ.get("HTTP_X_TENANT") or "").strip()[:64]
                  or DEFAULT_TENANT)
        raw_slo = ((_qs_last(qs, "slo") or environ.get("HTTP_X_SLO")
                    or "").strip())
        slo_class = raw_slo or "interactive"
        raw_deadline = (_qs_last(qs, "deadline_ms")
                        or environ.get("HTTP_X_DEADLINE_MS"))
        try:
            deadline_ms = float(raw_deadline) if raw_deadline else None
        except ValueError:
            return ("400 Bad Request",
                    b'{"error": "deadline_ms must be a number"}',
                    "application/json")
        explicit_deadline = deadline_ms is not None and deadline_ms > 0
        if not explicit_deadline:
            deadline_ms = 1e3 * self.slo_classes.get(
                slo_class, self.slo_classes.get("interactive", 1.0))
        # Deadline enforcement is opt-in: a client that names an SLO class
        # gets the class's default deadline; X-Deadline-Ms / ?deadline_ms=
        # tightens it. Requests carrying neither are not deadline-bounded
        # (a bare request must not 504 on a cold-start compile it never
        # asked to bound) — they still meet quota and the backlog gate.
        slo_deadline = (t0 + deadline_ms / 1e3
                        if (explicit_deadline or raw_slo) else None)

        def resolve():
            try:
                return self.registry.acquire(spec), None
            except UnknownModel as e:
                return None, (
                    "404 Not Found",
                    json.dumps({"error": str(e.args[0] if e.args else e)}).encode(),
                    "application/json",
                )
            except ModelNotServing as e:
                return None, (
                    "503 Service Unavailable",
                    json.dumps({"error": str(e)}).encode(),
                    "application/json",
                )

        # Resolve the model FIRST — an unknown-model 404 / draining 503
        # must fire before buffering up to max_body_mb of upload — and
        # hold an in-flight reference: a hot-swap started mid-request
        # drains the old version only after this reference drops, so the
        # request finishes against the engine it resolved. The body read +
        # multipart split happen once, BEFORE the attempt loop: a request
        # that coalesced onto a flight the registry retired mid-drain
        # retries against the NEW serving version, and the retry needs the
        # parsed uploads (the WSGI input stream can only be read once).
        mv, err = resolve()
        if err is not None:
            return err
        last_exc: BaseException | None = None
        try:
            # Validate topk's SYNTAX before buffering the body (a garbage
            # topk with a 32 MB upload must 400 without the read); the
            # per-model CLAMP happens in _predict_on — a coalesce retry
            # may resolve a different version with a different topk cap.
            try:
                topk_raw = _qs_last(qs, "topk")
                topk_req = int(topk_raw) if topk_raw is not None else None
            except ValueError:
                return ("400 Bad Request",
                        b'{"error": "topk must be an integer"}',
                        "application/json")
            # The stage keeps its start at t0, this method's entry (the
            # query and SLO parsing and the registry's acquire above are
            # in it, as http_ms_per_req has always read it); the
            # annotation covers the read alone.
            with stage(None, "body_read") as rd:
                body = self._read_body(environ)
            span.add("body_read", rd.t1 - t0)
            if body is None:
                return (
                    "413 Content Too Large",
                    json.dumps({"error": f"body exceeds {self.cfg.max_body_mb} MB cap"}).encode(),
                    "application/json",
                )
            ctype_in = environ.get("CONTENT_TYPE", "")
            if ctype_in.startswith("multipart/form-data"):
                named = _parse_multipart_files(body, ctype_in)
                if not named:
                    return "400 Bad Request", b'{"error": "no file part in multipart body"}', "application/json"
            else:
                named = [("body", body)]
            inm = environ.get("HTTP_IF_NONE_MATCH")
            # Chaos load spike: hold the request server-side BEFORE the
            # deadline anchor below, so the hold burns the client's SLO
            # budget (anchored at t0) and downstream admission sheds the
            # now-doomed request — exactly what a real ingress stall does.
            if self.chaos is not None:
                hold = self.chaos.spike_delay()
                if hold > 0.0:
                    time.sleep(hold)
            # ONE deadline across both attempts — a retry after a slow
            # aborted flight must not double the operator-configured
            # request timeout — anchored AFTER the body read, so a slow
            # (but within-read-deadline) upload does not eat the
            # inference budget. A client-carried SLO deadline tightens it.
            deadline = time.monotonic() + self.cfg.request_timeout_s
            if slo_deadline is not None:
                deadline = min(deadline, slo_deadline)
            for attempt in (0, 1):
                if mv is None:  # retry: re-resolve (the NEW version after a swap)
                    mv, err = resolve()
                    if err is not None:
                        return err
                try:
                    span.note("model", mv.ref)
                    resp = self._predict_on(qs, span, t0, mv, named, inm,
                                            deadline, topk_req,
                                            tenant=tenant,
                                            slo_class=slo_class,
                                            slo_deadline=slo_deadline)
                    if self.admission is not None and (
                            resp[0].startswith("2")
                            or resp[0].startswith("304")):
                        self.admission.count_admit(tenant, slo_class)
                    return resp
                except _CoalesceRetry as e:
                    last_exc = e.__cause__ or e
                finally:
                    self.registry.release(mv)
                    mv = None
            return (
                "503 Service Unavailable",
                json.dumps({
                    "error": "coalesced computation aborted twice: "
                             f"{type(last_exc).__name__}: {last_exc}"
                }).encode(),
                "application/json",
            )
        finally:
            if mv is not None:  # early return before/without the loop
                self.registry.release(mv)

    def _pipeline_predict(self, environ, name):
        """POST /pipelines/{name}: one image through a pipeline DAG as a
        single device-resident request — the composition /predict would
        need two round trips (and a host crop/re-encode) for. Accepts
        the same body forms as /predict but exactly ONE image; ?topk=
        clamps against the FINAL stage's model. The ETag is the final
        stage's cache identity, so If-None-Match works across the
        composition exactly like single-model caching."""
        t0 = time.monotonic()
        # twdlint: disable=pairing(span comes from environ and is finished by its owner — same contract as _predict)
        span = environ.get("tpu_serve.span") or Span()
        qs = urllib.parse.parse_qs(
            environ.get("QUERY_STRING", ""), keep_blank_values=True)
        try:
            topk_raw = _qs_last(qs, "topk")
            topk_req = int(topk_raw) if topk_raw is not None else None
        except ValueError:
            return ("400 Bad Request",
                    b'{"error": "topk must be an integer"}',
                    "application/json")
        body = self._read_body(environ)
        span.add("body_read", time.monotonic() - t0)
        if body is None:
            return ("413 Content Too Large",
                    json.dumps({"error":
                                f"body exceeds {self.cfg.max_body_mb} MB cap"
                                }).encode(),
                    "application/json")
        ctype_in = environ.get("CONTENT_TYPE", "")
        if ctype_in.startswith("multipart/form-data"):
            named = _parse_multipart_files(body, ctype_in)
            if len(named) != 1:
                return ("400 Bad Request",
                        json.dumps({"error": "pipelines take exactly one "
                                    f"image per request, got {len(named)}"
                                    }).encode(),
                        "application/json")
            data = named[0][1]
        else:
            data = body
        if not data:
            return ("400 Bad Request", b'{"error": "empty request body"}',
                    "application/json")
        try:
            payload, etag, meta = self.pipelines.execute(
                name, data, topk_req, span,
                deadline_s=self.cfg.request_timeout_s)
        except KeyError:
            return ("404 Not Found",
                    json.dumps({"error": f"unknown pipeline '{name}'",
                                "pipelines": self.pipelines.names()
                                }).encode(),
                    "application/json")
        except PipelineUnavailable as e:
            return ("503 Service Unavailable",
                    json.dumps({"error": str(e)}).encode(),
                    "application/json")
        except ValueError as e:
            return ("400 Bad Request",
                    json.dumps({"error": str(e)}).encode(),
                    "application/json")
        inm = environ.get("HTTP_IF_NONE_MATCH")
        headers = [("ETag", f'"{etag}"')]
        if inm is not None and etag in {
                t.strip().strip('"') for t in inm.split(",")}:
            return "304 Not Modified", b"", "application/json", headers
        resp = dict(payload)
        resp["pipeline"] = name
        resp["stages"] = meta["stages"]
        resp["latency_ms"] = round((time.monotonic() - t0) * 1e3, 3)
        resp["trace_id"] = span.trace_id
        return ("200 OK", json.dumps(resp).encode(), "application/json",
                headers)

    def _predict_on(self, qs, span, t0, mv, named, inm, deadline, topk_req,
                    tenant=DEFAULT_TENANT, slo_class="interactive",
                    slo_deadline=None):
        """The /predict body against one resolved model version.
        ``deadline`` is the request-wide await bound, owned by _predict so
        a coalesce retry cannot extend it; ``topk_req`` is the client's
        already-parsed topk (None = model default), clamped here because
        the cap is per-model. ``slo_deadline`` is the client's admission
        deadline (monotonic), threaded into batcher.lease so doomed
        requests shed before spending decode or device time."""
        model_cfg = mv.model_cfg
        batcher = mv.batcher
        # One clamp shared with the bulk tier: the clamped topk is part of
        # the cache key, so the key spaces stay identical (jobs.clamp_topk).
        topk = clamp_topk(topk_req, model_cfg)
        if batcher is None:  # construction without a batcher: draining
            return (
                "503 Service Unavailable",
                b'{"error": "no batcher attached"}',
                "application/json",
            )
        # Degradation ladder: one pressure observation per request against
        # the live batcher's queue fraction. Rung 1 clamps topk (smaller
        # payloads, cheaper postprocess + cache entries), rung 2 collapses
        # staging to the smallest canvas bucket, rung 3 sheds cache-miss
        # work (hits and coalesced waits still ride — the cheap traffic
        # that keeps goodput up is exactly what survives last).
        level = 0
        if self.pressure is not None:
            capq = (getattr(batcher, "max_queue", 0)
                    or getattr(batcher, "_max_pending", 0) or 0)
            depth = getattr(batcher, "queue_depth", 0)
            level = self.pressure.observe_pressure(
                (depth / capq) if capq else 0.0)
            if level >= 1 and topk:
                topk = min(topk, 1)
            # Quant-reroute rung (4-rung ladders only): before shedding
            # anything, route this request to a loaded int8 variant of
            # the same network — the raw-speed tier answers within the
            # parity-gate tolerance at a fraction of the device time.
            # Depth-1 recursion by construction: quant_variant() returns
            # None when the resolved model already serves int8.
            qlvl = self.pressure.quant_level
            if (qlvl is not None and level >= qlvl
                    and hasattr(self.registry, "quant_variant")):
                alt = self.registry.quant_variant(mv.name)
                if alt is not None:
                    try:
                        with self.registry.lease_model(alt.name) as amv:
                            self.pressure.count_reroute(len(named))
                            span.note("quant_reroute", amv.name)
                            return self._predict_on(
                                qs, span, t0, amv, named, inm, deadline,
                                topk_req, tenant=tenant, slo_class=slo_class,
                                slo_deadline=slo_deadline)
                    except (UnknownModel, ModelNotServing):
                        pass  # variant swapped/retired under us: serve here
        # Cap at the LIVE batcher's max (can be below engine.max_batch):
        # keeps one request's images inside a single batch assembly window.
        cap = batcher.max_batch
        if len(named) > cap:
            return (
                "413 Content Too Large",
                json.dumps({"error": f"at most {cap} images per request"}).encode(),
                "application/json",
            )

        span.note("images", len(named))
        cache = self.cache if self.cache.enabled else None
        # Shed level is ladder-relative: the LAST rung rejects cache-miss
        # work (level 3 legacy, 4 once a quant-reroute rung is configured);
        # hits and coalesced waits still ride.
        reject_level = (self.pressure.reject_level
                        if self.pressure is not None else 3)
        buckets = self.cfg.canvas_buckets
        if level >= 2 and len(buckets) > 1:
            # Rung 2: every image lands in the smallest canvas bucket —
            # less decode work and denser batches. The bucket set is part
            # of the cache key, so the rung's answers are entries of their
            # own, beside those made with every bucket to choose from.
            buckets = buckets[:1]
        # Stage every image before waiting on any (serving/staging.py):
        # slots land in the same batch-assembly window, so
        # same-canvas-bucket images typically share one device dispatch
        # (mixed buckets split by design — builders are per canvas shape).
        # Each staged image becomes one slot: a cached payload ("done"), a
        # coalesced wait on another request's in-flight computation
        # ("wait"), or this request's own batch future ("own").
        slots: list[tuple] = []

        def refuse(msg):
            # stage_image left nothing behind for the image it refused; the
            # request's earlier slots become padded holes and their led
            # flights abort, before any answer.
            abort_slots(slots, cache, RuntimeError(msg))
            return ("400 Bad Request", json.dumps({"error": msg}).encode(),
                    "application/json")

        # Stamped at zero first: a request refused before any decode still
        # counts in the stage's histogram, as it always has.
        span.add("image_decode", 0.0)
        try:
            for i, (fname, data) in enumerate(named):
                where = ("request body" if len(named) == 1
                         else f"file '{fname}' (#{i})")
                if not data:
                    return refuse(f"empty {where}")
                slots.append(stage_image(
                    data, batcher=batcher, mv=mv, cache=cache, topk=topk,
                    buckets=buckets, span=span, tenant=tenant,
                    deadline=slo_deadline, chaos=self.chaos,
                    shed_misses=level >= reject_level))
        except UndecodableImage as e:
            return refuse(f"could not decode image: {where}{e.note}")
        except ShuttingDown as e:
            abort_slots(slots, cache, e)
            return (
                "503 Service Unavailable",
                b'{"error": "server shutting down"}',
                "application/json",
            )
        except (BacklogFull, QuotaExceeded, DeadlineExceeded, Degraded) as e:
            # Fast rejects (503 / 429 / 504 / 503) with a machine-readable
            # reason and Retry-After, in microseconds instead of queueing
            # the upload toward the request timeout.
            abort_slots(slots, cache, e)
            return self._shed_response(e, tenant, slo_class)
        except BaseException as e:
            # A PENDING slot would hold its whole builder back (stalling
            # every sibling request) until the lease timeout; release
            # before the request-level 500 handler answers.
            abort_slots(slots, cache, e)
            raise
        payloads: list = [None] * len(slots)
        etags: list = [None] * len(slots)
        n_hit = n_wait = 0
        try:
            # OWN slots first, regardless of upload order: a leader must
            # publish its result to the cache (waking every coalesced
            # waiter on OTHER requests) before this request blocks on any
            # foreign flight — otherwise a slow unrelated flight earlier
            # in the upload order would stall waiters on a computation
            # that already finished, and a 504 here would discard it.
            for i, slot in enumerate(slots):
                kind = slot[0]
                if kind == "done":
                    n_hit += 1
                    payloads[i], etags[i] = slot[1], slot[2]
                elif kind == "own":
                    _, future, orig, flight, _lease = slot
                    # An annotation only: the batcher stamps queue_wait and
                    # the device_* stages over this interval, and the stage
                    # sum must keep tiling the request's wall time.
                    with stage(None, "await_batch", trace_id=span.trace_id):
                        row = future.result(
                            timeout=max(0.0, deadline - time.monotonic())
                        )
                    with stage(span, "postprocess"):
                        payload = self._format_row(row, orig, topk, mv)
                    if flight is not None:
                        # Leader: publish to the cache, wake every waiter.
                        etags[i] = self.cache.complete(flight, payload)
                    payloads[i] = payload
            for i, slot in enumerate(slots):
                if slot[0] != "wait":
                    continue
                n_wait += 1
                flight = slot[1]
                try:
                    with stage(span, "cache_wait"):
                        payload, etag = flight.future.result(
                            timeout=max(0.0, deadline - time.monotonic())
                        )
                except FutureTimeout:
                    raise
                except BaseException as e:
                    # The flight aborted under us — its version retired
                    # mid-drain (CacheRetired) or its leader failed. Fall
                    # through to a miss: _predict re-resolves the model
                    # (the NEW version after a swap) and retries this
                    # request once; this request's own results above are
                    # already cached, so the retry hits them.
                    raise _CoalesceRetry(e) from e
                payloads[i], etags[i] = payload, etag
        except FutureTimeout:
            # Undispatched slots become padded holes instead of wasting a
            # device dispatch on a request nobody is waiting for; led
            # flights abort so coalesced waiters fail over immediately.
            abort_slots(slots, cache, TimeoutError("inference timed out"))
            return self._shed_response(
                DeadlineExceeded("inference timed out"), tenant, slo_class)
        except DeadlineExceeded as e:
            # A seal-time shed: the batcher flipped this lease to a hole
            # because its deadline passed while it waited for dispatch.
            # Same 504 + reason as an admission-time shed — the client
            # cannot tell (and should not care) which side of the seal
            # the deadline crossed.
            abort_slots(slots, cache, e)
            return self._shed_response(e, tenant, slo_class)
        except ShuttingDown as e:
            # 503, not 500: the standard draining signal — load balancers
            # retry another backend instead of flagging an application bug.
            abort_slots(slots, cache, e)
            return (
                "503 Service Unavailable",
                b'{"error": "server shutting down"}',
                "application/json",
            )
        except _CoalesceRetry as e:
            abort_slots(slots, cache, e.__cause__ or e)
            raise
        except BaseException as e:
            # Any other failure (expired lease, poisoned batch): the led
            # flights must abort before the 500 propagates, or waiters
            # would hang to their own timeouts.
            abort_slots(slots, cache, e)
            raise
        extra_headers: list[tuple[str, str]] = []
        if cache is not None:
            token = ("hit" if n_hit == len(slots)
                     else ("coalesced" if n_wait else "miss"))
            if len(slots) > 1:
                # Per-image accounting for batch clients: the token alone
                # would collapse a 7-of-8-hit request to "miss" and make
                # client-side hit rates read near zero at high
                # files-per-request; loadgen parses the suffix into an
                # image-weighted hit rate.
                token += f"; hits={n_hit}/{len(slots)}"
            extra_headers.append(("X-Cache", token))
        # Batch clients get a stable shape: >1 file, or an explicit
        # ``?batch=1``, returns {"results": [...]} even for one image — so
        # a dynamically-assembled batch of size 1 doesn't change schema.
        with stage(span, "postprocess"):
            if len(payloads) == 1 and _qs_last(qs, "batch") != "1":
                # ETag = response digest (stable content identity: the
                # formatted payload + serving version — never the envelope,
                # whose latency/trace fields vary per request).
                etag = etags[0] or payload_etag(payloads[0], mv.name,
                                                mv.version)
                extra_headers.append(("ETag", f'"{etag}"'))
                if _etag_matches(inm, etag):
                    # The client already holds exactly this content: 304
                    # with no body. On a warm cache this costs a decode +
                    # digest + lookup — no device work, no serialization.
                    return ("304 Not Modified", b"", "application/json",
                            extra_headers)
                # Copy before the envelope update: a cached payload dict is
                # shared across responses and must never be mutated.
                resp = dict(payloads[0])
            else:
                # One result per file part, in upload order — the same
                # per-image objects a single-image call returns.
                resp = {"results": payloads}
        with stage(span, "serialize") as ser:
            resp.update(
                model=mv.name,
                model_version=mv.version,
                latency_ms=round(1e3 * (ser.t0 - t0), 2),
                # The trace ID in the body too, so a client that logs
                # response JSON (loadgen does) can join against the server
                # access log without plumbing headers through.
                trace_id=span.trace_id,
            )
            body = json.dumps(resp).encode()
        return "200 OK", body, "application/json", extra_headers

    _SHED_STATUS = {
        SHED_BACKLOG: "503 Service Unavailable",
        SHED_QUOTA: "429 Too Many Requests",
        SHED_DEADLINE: "504 Gateway Timeout",
        SHED_DEGRADED: "503 Service Unavailable",
    }

    def _shed_response(self, e, tenant=DEFAULT_TENANT,
                       slo_class="interactive"):
        """The uniform shed answer: machine-readable ``reason`` in the
        JSON body plus a Retry-After header on EVERY rejection path —
        backlog (503), quota (429), deadline (504), degraded (503) — and
        the per-tenant/per-class shed counter bump. By construction sheds
        are answered before decode or device time is spent, so this path
        must stay allocation-light and fast."""
        if isinstance(e, BacklogFull):
            reason = SHED_BACKLOG
        elif isinstance(e, QuotaExceeded):
            reason = SHED_QUOTA
        elif isinstance(e, DeadlineExceeded):
            reason = SHED_DEADLINE
        else:
            reason = SHED_DEGRADED
        retry = float(getattr(e, "retry_after_s", 1.0) or 1.0)
        if self.admission is not None:
            self.admission.count_shed(tenant, slo_class, reason)
        return (
            self._SHED_STATUS[reason],
            json.dumps({
                "error": str(e),
                "reason": reason,
                "retry_after_s": round(retry, 1),
            }).encode(),
            "application/json",
            [("Retry-After", str(max(1, int(round(retry)))))],
        )

    def _format_row(self, row, orig_hw, topk: int, mv) -> dict:
        """One image's batcher row → its JSON payload. The formatter lives
        in serving/jobs.py (format_result_row) so the interactive path and
        the bulk job runner can never drift apart on response shape."""
        return format_result_row(row, orig_hw, topk, mv)

    def _history(self, environ):
        """GET /debug/history?series=a,b&last_s=N&res=1s|10s|60s — bounded
        rows from the telemetry rings. Without ``series`` it answers the
        catalog (names only), never the full data: every response stays
        small enough to poll at 1 Hz."""
        if self.telemetry is None:
            return ("404 Not Found",
                    b'{"error": "telemetry disabled (--telemetry-interval 0)"}',
                    "application/json")
        qs = urllib.parse.parse_qs(
            environ.get("QUERY_STRING", ""), keep_blank_values=True
        )
        try:
            raw = _qs_last(qs, "last_s")
            last_s = float(raw) if raw is not None else 300.0
        except ValueError:
            return ("400 Bad Request",
                    b'{"error": "last_s must be a number"}',
                    "application/json")
        names_raw = _qs_last(qs, "series")
        if not names_raw:
            doc = {
                "series": self.telemetry.series_names(),
                "hint": "GET /debug/history?series=a,b&last_s=300&res=10s",
            }
            return "200 OK", json.dumps(doc, indent=2).encode(), "application/json"
        names = [n for n in names_raw.split(",") if n]
        if len(names) > 16:
            return ("400 Bad Request",
                    b'{"error": "at most 16 series per query"}',
                    "application/json")
        try:
            doc = self.telemetry.query(
                names, last_s=last_s, res=_qs_last(qs, "res") or None)
        except KeyError as e:
            body = json.dumps({"error": f"unknown series {e.args[0]!r}",
                               "series": self.telemetry.series_names()})
            return "400 Bad Request", body.encode(), "application/json"
        except ValueError as e:
            return ("400 Bad Request",
                    json.dumps({"error": str(e)}).encode(),
                    "application/json")
        return "200 OK", json.dumps(doc).encode(), "application/json"

    def _events(self, environ):
        """GET /debug/events?last_s=N&kind=a,b — the structured event
        ring, newest last. The ring is bounded (deque cap), so the
        response is too."""
        if self.telemetry is None:
            return ("404 Not Found",
                    b'{"error": "telemetry disabled (--telemetry-interval 0)"}',
                    "application/json")
        qs = urllib.parse.parse_qs(
            environ.get("QUERY_STRING", ""), keep_blank_values=True
        )
        try:
            raw = _qs_last(qs, "last_s")
            last_s = float(raw) if raw is not None else None
        except ValueError:
            return ("400 Bad Request",
                    b'{"error": "last_s must be a number"}',
                    "application/json")
        kinds_raw = _qs_last(qs, "kind")
        kinds = set(k for k in kinds_raw.split(",") if k) if kinds_raw else None
        doc = {
            "now": round(time.monotonic(), 3),
            "clock": "monotonic",
            "events": self.telemetry.events(last_s, kinds),
        }
        return "200 OK", json.dumps(doc).encode(), "application/json"

    def _trace_export(self, environ):
        """GET /debug/trace?last_s=N — the exportable trace timeline: every
        serving model's batch-lifecycle ring (one track per pipeline stage,
        one execute/transfer track per replica, bulk batches tagged) plus
        the flight recorder's recent request spans, serialized as
        Chrome-trace JSON. Overlap claims (decode(N+1) ∥ execute(N), bulk
        vs interactive alternation) become a file anyone can open in
        Perfetto instead of a bench number taken on faith."""
        qs = urllib.parse.parse_qs(
            environ.get("QUERY_STRING", ""), keep_blank_values=True
        )
        try:
            raw = _qs_last(qs, "last_s")
            requested_s = float(raw) if raw is not None else None
        except ValueError:
            return ("400 Bad Request",
                    b'{"error": "last_s must be a number"}',
                    "application/json")
        # ONE window clamp for the whole export (utils/tracing.py): the
        # request window, the recent ring's actual retention, and the
        # 1 h cap all meet in effective_window, and the response reports
        # what it actually covered instead of silently truncating.
        last_s = effective_window(
            requested_s, self.obs.flight.retention_s())
        models = []
        for mv in self.registry.serving_entries():
            tl = getattr(mv.batcher, "batch_timeline", None)
            if tl is None:
                continue
            models.append({"name": f"{mv.name}@{mv.version}",
                           "timeline": tl()})
        events = (self.telemetry.events(last_s)
                  if self.telemetry is not None else None)
        doc = chrome_trace(models, self.obs.flight.trace_records(last_s),
                           last_s=last_s, instants=events)
        doc["otherData"]["requested_window_s"] = requested_s
        doc["otherData"]["effective_window_s"] = last_s
        return "200 OK", json.dumps(doc).encode(), "application/json"

    def _trace(self, environ):
        """POST /debug/trace?ms=N[&dir=D][&python=1] — record a
        ``jax.profiler`` trace of this process for N ms. The recording
        holds the device's ops, JAX's own host events and the program's
        ``twd.*`` annotations (utils/tracing.py::stage); the Python tracer
        (a ``$file:line`` event per call) is off unless ``python=1``: it
        slows the window it records and costs gigabytes of host memory.
        A ``twd.clock`` marker right after the start and right before the
        stop carries ``time.monotonic_ns()``, which puts ``GET
        /debug/trace`` and the batch records on the recording's clock.
        ``/stats → profile`` keeps the last recording's interval and a
        copy of every batch record that met it. One recording at a time:
        a second POST meanwhile answers 409."""
        qs = urllib.parse.parse_qs(
            environ.get("QUERY_STRING", ""), keep_blank_values=True
        )
        try:
            ms_raw = _qs_last(qs, "ms")
            ms = min(int(ms_raw) if ms_raw is not None else 1000, 60_000)
        except ValueError:
            return "400 Bad Request", b'{"error": "ms must be an integer"}', "application/json"
        out_dir = _qs_last(qs, "dir") or "/tmp/tpu_serve_trace"
        python_tracer = _qs_last(qs, "python") == "1"
        with self._profile_lock:
            if self._profiling:
                return ("409 Conflict",
                        b'{"error": "a trace is already being recorded"}',
                        "application/json")
            self._profiling = True
        import jax

        try:
            options = jax.profiler.ProfileOptions()
            options.python_tracer_level = 1 if python_tracer else 0
            jax.profiler.start_trace(out_dir, profiler_options=options)
            try:
                t_start = clock_marker()
                time.sleep(ms / 1e3)
                t_stop = clock_marker()
            finally:
                jax.profiler.stop_trace()
            batches = [
                {**rec, "model": f"{mv.name}@{mv.version}"}
                for mv in self.registry.serving_entries()
                if hasattr(mv.batcher, "batch_timeline")
                for rec in mv.batcher.batch_timeline()
                if rec["t_open"] <= t_stop
                and (rec["t_done"] is None or rec["t_done"] >= t_start)
            ]
            profile = {"t_start": t_start, "t_stop": t_stop,
                       "python_tracer": python_tracer, "trace_dir": out_dir,
                       "batches": batches}
        finally:
            with self._profile_lock:
                self._profiling = False
        self._profile = profile
        return "200 OK", json.dumps({"trace_dir": out_dir, "captured_ms": ms}).encode(), "application/json"


# ------------------------------------------------------------------ server


class HttpCounters:
    """Lock-guarded keep-alive effectiveness counters, exported by /stats.
    ``requests_per_connection`` near 1.0 means clients are not reusing
    connections (keep-alive off or HTTP/1.0 clients) and the handshake tax
    is being paid per image."""

    def __init__(self):
        self._lock = named_lock("http.counters_lock")
        self._connections = 0
        self._requests = 0
        self._active = 0

    def connection_opened(self):
        with self._lock:
            self._connections += 1
            self._active += 1

    def connection_closed(self):
        with self._lock:
            self._active -= 1

    def request_served(self):
        with self._lock:
            self._requests += 1

    def snapshot(self) -> dict:
        with self._lock:
            conns, reqs, active = self._connections, self._requests, self._active
        return {
            "connections_total": conns,
            "requests_total": reqs,
            "active_connections": active,
            "requests_per_connection": round(reqs / conns, 2) if conns else None,
        }


class _BodyReader:
    """Bounded view of the connection's rfile: reads never run past the
    declared Content-Length (keep-alive framing depends on it), and the
    handler can drain whatever the app left unread so the next request on
    the connection starts at a request line, not mid-body."""

    def __init__(self, rfile, length: int):
        self._rfile = rfile
        self.remaining = max(0, length)

    def read(self, n: int = -1) -> bytes:
        if n is None or n < 0 or n > self.remaining:
            n = self.remaining
        if n <= 0:
            return b""
        data = self._rfile.read(n)
        self.remaining -= len(data)
        return data

    def drain(self):
        while self.remaining > 0:
            if not self.read(min(65536, self.remaining)):
                break  # peer went away; connection closes anyway


def _wait_readable(sock, timeout_s: float) -> bool:
    """poll(), not select(): select.select raises ValueError for any fd
    >= FD_SETSIZE (1024), which a serving process with many device/model
    fds can exceed under a connection spike."""
    if hasattr(select, "poll"):
        p = select.poll()
        p.register(sock, select.POLLIN)
        return bool(p.poll(max(0.0, timeout_s) * 1000))
    readable, _, _ = select.select([sock], [], [], max(0.0, timeout_s))
    return bool(readable)


class _DeadlineFile:
    """Buffered read side of the connection enforcing a TOTAL deadline
    across reads.

    With a bounded worker pool, a client trickling one header byte per
    interval would pin a worker forever: each byte resets the per-recv
    socket timeout, and a single stdlib ``BufferedReader.readline`` spans
    arbitrarily many raw recvs inside one call — so the cap must live at
    the raw-read level, not around the buffered call. Reads block in
    ``select`` bounded by the armed deadline; expiry raises
    ``socket.timeout``, which the base parser (headers) and the app (body)
    already handle by closing the connection."""

    def __init__(self, connection, base_timeout: float):
        self._conn = connection
        self._base = base_timeout
        self._buf = bytearray()
        self._eof = False
        self.deadline: float | None = None  # armed per request by handle()

    def _cap(self) -> float:
        if self.deadline is not None:
            return self.deadline
        return time.monotonic() + self._base

    def _fill(self, deadline: float) -> bool:
        """Pull more bytes into the buffer: True on data, False on EOF,
        ``socket.timeout`` when the deadline expires first."""
        if self._eof:
            return False
        remaining = deadline - time.monotonic()
        if remaining <= 0 or not _wait_readable(self._conn, remaining):
            raise socket.timeout("request read deadline exceeded")
        chunk = self._conn.recv(65536)
        if not chunk:
            self._eof = True
            return False
        self._buf += chunk
        return True

    def readline(self, limit: int = -1) -> bytes:
        deadline = self._cap()
        while True:
            i = self._buf.find(b"\n")
            if i >= 0 and (limit < 0 or i < limit):
                n = i + 1
            elif limit >= 0 and len(self._buf) >= limit:
                n = limit  # stdlib semantics: over-limit line comes back cut
            elif self._fill(deadline):
                continue
            else:
                n = len(self._buf)  # EOF: hand back whatever arrived
            out = bytes(self._buf[:n])
            del self._buf[:n]
            return out

    def read(self, n: int = -1) -> bytes:
        deadline = self._cap()
        if n is None or n < 0:
            out = bytes(self._buf)  # read-to-EOF is never used mid-request
            self._buf.clear()
            return out
        while len(self._buf) < n:
            if not self._fill(deadline):
                break
        out = bytes(self._buf[:n])
        del self._buf[:n]
        return out

    def peek(self, n: int = 1) -> bytes:
        return bytes(self._buf[:n])  # never blocks: buffered bytes only

    def close(self):  # the handler owns the socket's lifetime
        pass


class KeepAliveWSGIHandler(BaseHTTPRequestHandler):
    """One worker-owned connection: any number of HTTP/1.1 requests, each
    translated to a WSGI call on the server's app.

    ``BaseHTTPRequestHandler.handle`` already loops ``handle_one_request``
    until ``close_connection`` — with ``protocol_version = HTTP/1.1`` and a
    Content-Length on every response, persistence is the default and a
    client's ``Connection: close`` is honored by the base parser.
    """

    protocol_version = "HTTP/1.1"
    server_version = "tpu-serve"
    sys_version = ""  # never advertise the Python patch level
    # Responses go out as two writes (headers flush, then body); with
    # Nagle on, the body write stalls behind the client's delayed ACK
    # (~40 ms) on real links — on the keep-alive hot path, per request.
    disable_nagle_algorithm = True
    # Unread request-body bytes worth consuming to keep a connection alive;
    # past this (e.g. a 413'd oversized upload) closing is cheaper.
    max_drain = 1 << 20

    def setup(self):
        self.timeout = self.server.keepalive_timeout_s  # idle keep-alive cap
        self._counted = False
        self._responded = False
        super().setup()
        # Total read budget per REQUEST (headers + body), not per recv —
        # see _DeadlineFile. Reuses the keep-alive timeout as the bound.
        self.rfile = _DeadlineFile(self.connection, self.timeout)
        self.server.track_connection(self.connection, opened=True)
        self.server.counters.connection_opened()
        self._counted = True

    def finish(self):
        try:
            super().finish()
        finally:
            if self._counted:
                self.server.track_connection(self.connection, opened=False)
                self.server.counters.connection_closed()

    def handle(self):
        """Keep-alive loop, but fair under oversubscription: between
        requests the worker polls rather than blocking the full keep-alive
        timeout, and closes an IDLE connection as soon as other accepted
        connections are waiting for a worker — otherwise ``pool_size``
        closed-loop clients would pin every worker and queued connections
        would starve until the client-side timeout."""
        self.close_connection = True
        # The FIRST request gets a fairness gate too — a client that
        # connects and sends nothing must not pin a worker for the whole
        # keep-alive timeout while accepted connections queue — but with a
        # grace window: its request bytes may legitimately still be in
        # flight (high-RTT links), and resetting a never-served connection
        # gives the client no response to retry on. Idle BETWEEN requests
        # has no grace: a keep-alive close there is ordinary and clients
        # reconnect.
        if not self._await_next_request(grace_s=1.0):
            return
        self._handle_with_deadline()
        while not self.close_connection:
            if not self._await_next_request():
                break
            self._handle_with_deadline()

    def _handle_with_deadline(self):
        self.rfile.deadline = time.monotonic() + self.server.request_read_timeout_s
        self._responded = False
        # Trace start: the request's bytes are known to be arriving (the
        # keep-alive wait is over), so header-read time is request work,
        # idle-connection time is not. The one stage that is not a ``with
        # stage(...)`` block: it ends in _run_app, once the headers have
        # named the span it belongs to, so clock and annotation
        # (``twd.http_read``) are opened by hand here and closed there (or
        # below, where the request never got that far).
        self._req_t0 = time.monotonic()
        self._read_ann = TraceAnnotation("twd.http_read")
        self._read_ann.__enter__()
        try:
            self.handle_one_request()
        finally:
            self._end_http_read()
            self.rfile.deadline = None

    def _end_http_read(self):
        ann, self._read_ann = getattr(self, "_read_ann", None), None
        if ann is not None:
            ann.__exit__(None, None, None)

    def send_response_only(self, code, message=None):
        # Every response funnels through here — including send_error's
        # 400/414/501 and the 411 early return — so /stats request counts
        # match what actually went over the wire. Counted HERE, before the
        # body flushes (not after handle_one_request returns): a client
        # that has read its response must find it already counted — the
        # same ordering invariant obs.finish documents.
        super().send_response_only(code, message)
        if not self._responded:
            self._responded = True
            self.server.counters.request_served()

    def _await_next_request(self, grace_s: float = 0.0) -> bool:
        if self._buffered_request_bytes():
            return True  # pipelined request already sitting in rfile
        now = time.monotonic()
        no_yield_before = now + grace_s
        deadline = now + self.server.keepalive_timeout_s
        while True:
            try:
                readable = _wait_readable(self.connection, 0.05)
            except (OSError, ValueError):
                return False  # connection torn down under us
            if readable:
                return True  # next request line (or EOF — handled by parser)
            now = time.monotonic()
            if self.server.draining:
                return False
            if now >= no_yield_before and not self.server._pending.empty():
                return False  # yield the worker to a queued connection
            if now >= deadline:
                return False

    def _buffered_request_bytes(self) -> bool:
        """Pipelined bytes already pulled into the rfile buffer are
        invisible to select; _DeadlineFile.peek never touches the socket."""
        return bool(self.rfile.peek(1))

    def do_GET(self):
        self._run_app()

    # The WSGI app routes on REQUEST_METHOD itself (405s what it doesn't
    # serve), so every method passes through — notably HEAD, which load
    # balancers probe /healthz with.
    do_POST = do_HEAD = do_PUT = do_DELETE = do_OPTIONS = do_GET

    def _run_app(self):
        path, _, query = self.path.partition("?")
        if self.headers.get("Transfer-Encoding"):
            # Chunked bodies aren't parsed here; without a trusted length the
            # next request's framing can't be found, so reject and close
            # rather than desync every later request on this connection.
            self.close_connection = True
            body = b'{"error": "Transfer-Encoding not supported; send Content-Length"}\n'
            self.send_response(411, "Length Required")
            self.send_header("Content-Type", "application/json")
            self.send_header("Content-Length", str(len(body)))
            self.send_header("Connection", "close")
            self.end_headers()
            self.wfile.write(body)
            return
        cl_header = self.headers.get("Content-Length")
        try:
            declared = int(cl_header) if cl_header is not None else 0
        except ValueError:
            declared = -1
        if declared < 0:
            # Garbage/negative framing: the app 413s it, and with no trusted
            # body length the connection cannot be reused afterwards.
            self.close_connection = True
        reader = _BodyReader(self.rfile, declared)
        # Span born at accept: trace ID propagated from a well-formed
        # inbound X-Trace-Id or minted fresh; the header read+parse that
        # just happened is the first stage.
        t0 = getattr(self, "_req_t0", None)
        span = Span(accept_trace_id(self.headers.get("X-Trace-Id")), t0=t0)
        span.add("http_read", time.monotonic() - span.t0)
        self._end_http_read()
        environ = {
            "REQUEST_METHOD": self.command,
            "PATH_INFO": urllib.parse.unquote(path),
            "QUERY_STRING": query,
            "SERVER_PROTOCOL": self.protocol_version,
            "SERVER_NAME": self.server.server_name,
            "SERVER_PORT": str(self.server.server_port),
            "REMOTE_ADDR": self.client_address[0],
            "CONTENT_TYPE": self.headers.get("Content-Type", ""),
            "CONTENT_LENGTH": cl_header if cl_header is not None else "",
            "wsgi.version": (1, 0),
            "wsgi.url_scheme": "http",
            "wsgi.input": reader,
            "wsgi.errors": sys.stderr,
            "wsgi.multithread": True,
            "wsgi.multiprocess": False,
            "wsgi.run_once": False,
            "tpu_serve.span": span,
        }
        # PEP 3333 HTTP_* request headers: embedded WSGI apps read these
        # (the wsgiref front end this pool replaced populated them too).
        # Repeats of a header comma-join, per the spec.
        for hk, hv in self.headers.items():
            key = "HTTP_" + hk.upper().replace("-", "_")
            if key in ("HTTP_CONTENT_TYPE", "HTTP_CONTENT_LENGTH"):
                continue  # already present under their CGI names
            environ[key] = f"{environ[key]},{hv}" if key in environ else hv

        captured = {}

        def start_response(status, headers, exc_info=None):
            captured["status"] = status
            captured["headers"] = headers

        body = b"".join(self.server.app(environ, start_response))
        status = captured.get("status", "500 Internal Server Error")
        code_s, _, reason = status.partition(" ")

        # Keep-alive framing: the next request starts where this body ends,
        # so unread request bytes are drained (small) or the connection is
        # closed (large — cheaper than reading a rejected upload).
        if reader.remaining:
            if reader.remaining <= self.max_drain:
                try:
                    reader.drain()
                except OSError:
                    # Stalled uploader: the declared body never arrived, so
                    # the connection can't be re-framed — still send the
                    # response the app produced, then close.
                    self.close_connection = True
            else:
                self.close_connection = True
        if self.server.draining:
            self.close_connection = True

        # Fold the completed span into the app's observability BEFORE the
        # response bytes go out: a client that has read its response is
        # guaranteed the very next /metrics scrape already counts it.
        # (The socket write itself is therefore not a span stage — it is
        # microseconds on the loopback/LAN paths this front end serves.)
        obs = getattr(self.server.app, "obs", None)
        if obs is not None:
            try:
                code_i = int(code_s)
            except ValueError:
                code_i = 500
            obs.finish(span, code_i)

        self.send_response(int(code_s), reason or None)
        have_length = have_trace = False
        for k, v in captured.get("headers", []):
            kl = k.lower()
            if kl == "content-length":
                have_length = True
            elif kl == "x-trace-id":
                have_trace = True
            self.send_header(k, v)
        if not have_length:
            self.send_header("Content-Length", str(len(body)))
        if not have_trace:
            # Stub/embedded WSGI apps that don't know about spans still get
            # the trace ID onto the wire.
            self.send_header("X-Trace-Id", span.trace_id)
        if self.close_connection:
            self.send_header("Connection", "close")
        self.end_headers()
        if self.command != "HEAD":  # headers (incl. length) only, per spec
            self.wfile.write(body)

    def log_message(self, fmt, *args):  # structured logging happens in App
        log.debug("%s " + fmt, self.address_string(), *args)


class PoolWSGIServer(TCPServer):
    """HTTP/1.1 keep-alive front end on a bounded worker pool.

    ``serve_forever`` only accepts and enqueues; a fixed pool of worker
    threads owns each connection for its whole lifetime and serves any
    number of requests on it. Closed-loop clients therefore pay the TCP
    handshake and the thread handoff once per CONNECTION, not once per
    request (the old ThreadingMixIn+wsgiref server spawned a thread and
    forced ``Connection: close`` per request). With more live connections
    than workers, an IDLE kept-alive connection yields its worker to a
    queued connection (closing early) so queued clients are served instead
    of starving behind keep-alive waits. Overload sheds at accept (pending
    queue full → connection closed) instead of queueing without bound — a
    reset is an honest signal a load balancer retries.
    """

    allow_reuse_address = True
    # Kernel accept backlog; the default (5) RSTs connections under
    # concurrent load.
    request_queue_size = 128

    def __init__(self, addr, app, pool_size: int = 16, keepalive_timeout_s: float = 15.0,
                 request_read_timeout_s: float = 30.0):
        self.app = app
        self.pool_size = max(1, pool_size)
        self.keepalive_timeout_s = keepalive_timeout_s
        # TOTAL per-request read budget (headers + body) — deliberately a
        # separate knob from keep-alive hygiene: lowering the idle timeout
        # must not cap how long a legitimate large upload may take.
        self.request_read_timeout_s = request_read_timeout_s
        self.counters = HttpCounters()
        self.draining = False
        self._conns_lock = named_lock("http.conns_lock")
        self._open_conns: set = set()
        self._pending: queue.Queue = queue.Queue(maxsize=self.pool_size * 4)
        super().__init__(addr, None)  # handlers are constructed by workers
        self._workers = [
            threading.Thread(target=self._worker, name=f"http-worker-{i}", daemon=True)
            for i in range(self.pool_size)
        ]
        for t in self._workers:
            t.start()

    # -- plumbing shared with wsgiref.WSGIServer ---------------------------

    def server_bind(self):
        super().server_bind()
        host, port = self.server_address[:2]
        self.server_name = socket.getfqdn(host)
        self.server_port = port

    def process_request(self, request, client_address):
        """Accept thread: hand the connection to the pool, never spawn."""
        try:
            self._pending.put_nowait((request, client_address))
        except queue.Full:
            self.shutdown_request(request)  # shed at the edge

    def finish_request(self, request, client_address):
        KeepAliveWSGIHandler(request, client_address, self)

    def handle_error(self, request, client_address):
        # Peer resets and truncated requests are client weather, not server
        # errors; keep them off stderr (the stdlib default prints a
        # traceback per aborted connection).
        log.debug("connection error from %s", client_address, exc_info=True)

    # -- worker pool -------------------------------------------------------

    def _worker(self):
        while True:
            try:
                item = self._pending.get(timeout=0.25)
            except queue.Empty:
                if self.draining:
                    return
                continue
            if item is None:
                return
            request, client_address = item
            try:
                self.finish_request(request, client_address)
            except Exception:
                self.handle_error(request, client_address)
            finally:
                self.shutdown_request(request)

    def track_connection(self, conn, *, opened: bool):
        with self._conns_lock:
            (self._open_conns.add if opened else self._open_conns.discard)(conn)

    def close_pool(self, grace_s: float = 10.0):
        """Drain the worker pool: stop keep-alive looping, half-close the
        read side of every open connection (a worker blocked waiting for the
        client's next request wakes immediately; responses in flight still
        write), then join workers within the grace budget."""
        self.draining = True
        with self._conns_lock:
            conns = list(self._open_conns)
        for c in conns:
            try:
                c.shutdown(socket.SHUT_RD)
            except OSError:
                pass  # already gone
        for _ in self._workers:
            try:
                self._pending.put_nowait(None)
            except queue.Full:
                break  # busy workers poll the draining flag instead
        deadline = time.monotonic() + grace_s
        for t in self._workers:
            t.join(timeout=max(0.0, deadline - time.monotonic()))
        # Connections accepted but never picked up by a worker would
        # otherwise stay open (client hangs) until process exit.
        while True:
            try:
                item = self._pending.get_nowait()
            except queue.Empty:
                break
            if item is not None:
                self.shutdown_request(item[0])


def make_http_server(app, host: str, port: int, pool_size: int = 16,
                     keepalive_timeout_s: float = 15.0,
                     request_read_timeout_s: float = 30.0) -> PoolWSGIServer:
    srv = PoolWSGIServer((host, port), app, pool_size=pool_size,
                         keepalive_timeout_s=keepalive_timeout_s,
                         request_read_timeout_s=request_read_timeout_s)
    if hasattr(app, "attach_http"):
        app.attach_http(srv)
    return srv


def shutdown_gracefully(srv, batcher, grace_s: float = 10.0,
                        jobs=None) -> None:
    """Ordered drain: stop accepting → checkpoint running bulk jobs →
    resolve every queued/in-flight request → let pool workers flush their
    responses and exit → close the listening socket.

    ``batcher`` is anything with the drain-on-``stop()`` contract — a
    single :class:`~.batcher.Batcher` or a whole
    :class:`~.registry.ModelRegistry` (which stops every model's batcher).
    ``jobs`` is the app's :class:`~.jobs.JobManager` (auto-discovered from
    ``srv.app`` when omitted): it stops FIRST, because its runner finishes
    its in-flight chunk against live batchers and writes the checkpoint an
    interrupted job resumes from — this is the SIGTERM path, and before it
    existed an in-flight bulk workload was silently lost.

    The order matters: worker threads block on batcher futures, so the
    batcher must stop (which dispatches everything already queued and
    resolves all futures) BEFORE the pool join — joining first would
    deadlock, and closing first would truncate responses the batcher is
    about to complete. Workers are daemons, so a client that stops reading
    can only delay exit by ``grace_s``, never hang it.
    """
    srv.shutdown()  # no-op if serve_forever already unwound (event is set)
    app = getattr(srv, "app", None)
    # Telemetry sampler first: it only READS the registry/batchers, so
    # stopping it before they drain means no tick ever observes a
    # half-stopped serving stack.
    telemetry = getattr(app, "telemetry", None)
    if telemetry is not None:
        telemetry.stop()
    if jobs is None:
        jobs = getattr(app, "jobs", None)
    if jobs is not None:
        jobs.stop(grace_s)
    batcher.stop()
    if hasattr(srv, "close_pool"):
        srv.close_pool(grace_s)
    srv.server_close()
