#!/usr/bin/env python
"""HTTP load generator for the /predict route (SURVEY.md §3.5, M5).

The north-star metrics are *client-side*: images/sec through the full HTTP
stack and p50/p99 end-to-end latency (BASELINE.json). Two modes:

- closed loop (default): N workers each keep exactly one request in flight —
  measures peak sustainable throughput and the latency that comes with it.
- open loop (--rate R): Poisson arrivals at R req/s regardless of response
  times — measures latency at a fixed offered load (no coordinated omission).

Usage:
    python tools/loadgen.py --url http://127.0.0.1:8500/predict \
        --images dir_of_jpegs/ --workers 16 --duration 30
    python tools/loadgen.py --rate 200 --duration 30   # open loop, synthetic

Prints one JSON summary line on stdout (throughput, p50/p90/p99, errors).

Heavy-tailed traffic: ``--zipf S`` draws each image Zipf(S)-skewed over
the corpus (``--corpus N`` sizes the synthetic one) — the hot-key
workload the server's content-addressed response cache serves. Against a
cache-enabled server the summary gains a ``cache`` block (hit rate,
per-hit/per-miss latency percentiles) built from the X-Cache response
headers.

Mesh-wide serving: start the server with a placement suffix on --model
(``python server.py --model mobilenet_v2,replicas=8`` replicates the model
across 8 device groups; ``--model inception_v3,shard=batch`` shards every
batch over the whole mesh — the default). Against a replicated placement
the summary gains ``replica_utilization`` (per-chip busy fraction + batch
count over the window, from the server's per-replica dispatch counters)
next to the stage-utilization table, so dispersion across chips is
visible without a profiler. ``--model-mix`` routing is unchanged — names
address models; placement is the server's concern.
"""

from __future__ import annotations

import argparse
import http.client
import io
import json
import os
import random
import re
import sys
import threading
import time
import urllib.error
import urllib.parse
import urllib.request
from pathlib import Path


def synthetic_jpegs(n: int = 8, size: int = 640) -> list[bytes]:
    """Deterministic photo-ish JPEGs (gradients + noise), no files needed."""
    import numpy as np
    from PIL import Image

    rng = np.random.RandomState(20260729)
    out = []
    for i in range(n):
        h = size - (i % 3) * 64
        w = size - (i % 4) * 48
        yy, xx = np.mgrid[0:h, 0:w].astype(np.float32)
        img = (
            np.stack(
                [yy * (0.2 + 0.1 * i), xx * 0.25, (yy + xx) * 0.15], axis=-1
            )
            + rng.rand(h, w, 3) * 30
        ).clip(0, 255).astype(np.uint8)
        buf = io.BytesIO()
        Image.fromarray(img).save(buf, "JPEG", quality=88)
        out.append(buf.getvalue())
    return out


def parse_sizes(s: str | None) -> list[tuple[tuple[int, int], float]] | None:
    """``--sizes WxH[:WEIGHT],...`` → [((w, h), weight), ...]: the
    mixed-size synthetic corpus spec, e.g. ``200x150:3,640x480:1`` for a
    75/25 small/large upload mix — the traffic shape ragged packing
    exists for (uploads smaller than the canvas bucket)."""
    if not s:
        return None
    out = []
    for part in s.split(","):
        part = part.strip()
        if not part:
            continue
        dims, _, w_s = part.partition(":")
        m = re.fullmatch(r"(\d+)[xX](\d+)", dims.strip())
        if not m:
            raise ValueError(f"bad --sizes entry {part!r} (want WxH[:WEIGHT])")
        try:
            weight = float(w_s) if w_s else 1.0
        except ValueError:
            raise ValueError(f"bad --sizes weight in {part!r}") from None
        if weight <= 0:
            raise ValueError(f"--sizes weight must be > 0 in {part!r}")
        out.append(((int(m.group(1)), int(m.group(2))), weight))
    if not out:
        raise ValueError(f"empty --sizes {s!r}")
    return out


def synthetic_jpegs_sized(sizes, per_size: int = 4):
    """Deterministic JPEGs at exactly the requested pixel sizes:
    ``(images, labels, weights)`` with ``per_size`` distinct images per
    (w, h), each labeled ``"WxH"`` and weighted so the PER-SIZE draw
    probability matches the spec's weights (split evenly across that
    size's images)."""
    import numpy as np
    from PIL import Image

    rng = np.random.RandomState(20260804)
    images, labels, weights = [], [], []
    for (w, h), wt in sizes:
        for i in range(per_size):
            yy, xx = np.mgrid[0:h, 0:w].astype(np.float32)
            img = (
                np.stack(
                    [yy * (0.2 + 0.07 * i), xx * 0.25, (yy + xx) * 0.15],
                    axis=-1,
                )
                + rng.rand(h, w, 3) * 30
            ).clip(0, 255).astype(np.uint8)
            buf = io.BytesIO()
            Image.fromarray(img).save(buf, "JPEG", quality=88)
            images.append(buf.getvalue())
            labels.append(f"{w}x{h}")
            weights.append(wt / per_size)
    return images, labels, weights


def load_images(path: str | None, n: int = 8) -> list[bytes]:
    if not path:
        return synthetic_jpegs(n=n)
    files = sorted(
        p for p in Path(path).iterdir() if p.suffix.lower() in (".jpg", ".jpeg", ".png")
    )
    if not files:
        sys.exit(f"no images in {path}")
    return [p.read_bytes() for p in files]


def zipf_weights(n: int, s: float) -> list[float]:
    """Unnormalized Zipf(s) weights over ``n`` ranks: item i gets
    1/(i+1)^s. The heavy-tailed image-key distribution real user traffic
    follows — at s≈1.1 the head keys dominate, which is exactly the
    workload the server's content-addressed response cache exists for.
    Rank == corpus index (deterministic), so repeat runs sample the same
    hot set."""
    return [1.0 / (i + 1) ** s for i in range(n)]


class Recorder:
    def __init__(self):
        self.lock = threading.Lock()
        self.latencies_ms: list[float] = []
        self.done_at: list[float] = []
        self.images_done: list[int] = []  # images per completed request
        self.errors = 0
        self.err_at: list[float] = []  # error timestamps (windowed analyses)
        self.connections = 0  # TCP connections opened (keep-alive telemetry)
        self.sample_error: str | None = None
        # Per-model completion/error counts under --model-mix: the check
        # that mixed traffic actually reached every model in the mix.
        self.per_model: dict = {}
        # Response-cache outcome per request, from the server's X-Cache
        # header: hit/miss/coalesced counts plus per-class latencies — the
        # client-side view of what the cache is worth (a hit answers in
        # HTTP time, a miss pays the device). The request token marks a
        # multi-image request "hit" only when EVERY image hit, so the
        # image-weighted split comes from the header's "hits=h/n" suffix.
        self.cache_counts = {"hit": 0, "miss": 0, "coalesced": 0}
        self.lat_by_cache: dict[str, list[float]] = {"hit": [], "miss": []}
        self.image_cache = {"hit": 0, "total": 0}
        # One X-Trace-Id from a successful response: the handle for joining
        # this run against the server's access log / flight recorder.
        self.sample_trace_id: str | None = None
        # Overload accounting (ISSUE 13): shed responses (429/503/504
        # carrying a machine-readable "reason") counted by reason and by
        # tenant, plus their ANSWER latencies — a shed is only graceful
        # if the rejection itself is fast. Sheds also count in `errors`
        # (the pre-existing goodput denominators must not change).
        self.sheds_by_reason: dict[str, int] = {}
        self.shed_latencies_ms: list[float] = []
        # Per-tenant ledger under --tenants: admit/shed/error counts and
        # admitted-request latencies, keyed by the X-Tenant value sent.
        self.per_tenant: dict[str, dict] = {}
        # Per-size latencies under --sizes ("WxH" label per single-image
        # request): the mixed-size view ragged packing is judged by.
        self.per_size: dict[str, list[float]] = {}

    def _tenant(self, tenant: str) -> dict:
        return self.per_tenant.setdefault(
            tenant, {"completed": 0, "shed": 0, "errors": 0, "lat": []})

    def ok(self, ms: float, images: int = 1, trace_id: str | None = None,
           model: str | None = None, cache: str | None = None,
           tenant: str | None = None, size: str | None = None):
        with self.lock:
            self.latencies_ms.append(ms)
            self.done_at.append(time.perf_counter())
            self.images_done.append(images)
            if size is not None:
                self.per_size.setdefault(size, []).append(ms)
            if tenant is not None:
                t = self._tenant(tenant)
                t["completed"] += 1
                t["lat"].append(ms)
            if model is not None:
                m = self.per_model.setdefault(model, {"completed": 0, "errors": 0})
                m["completed"] += 1
            if cache:
                token, _, rest = cache.partition(";")
                token = token.strip()
                if token in self.cache_counts:
                    self.cache_counts[token] += 1
                    # Coalesced requests paid (a share of) the device wait:
                    # they group with misses for the latency split.
                    self.lat_by_cache[
                        "hit" if token == "hit" else "miss"
                    ].append(ms)
                    m = re.search(r"hits=(\d+)/(\d+)", rest)
                    if m:  # batch request: per-image split from the server
                        h, n = int(m.group(1)), int(m.group(2))
                    else:
                        h, n = (images if token == "hit" else 0), images
                    self.image_cache["hit"] += h
                    self.image_cache["total"] += n
            if trace_id and self.sample_trace_id is None:
                self.sample_trace_id = trace_id

    def connected(self):
        with self.lock:
            self.connections += 1

    def images_completed_by(self, t: float) -> int:
        """Images finished at or before ``t`` — the lock and the parallel
        done_at/images_done arrays live here so every consumer (this CLI's
        summary, bench.py's http_bench) counts the same way."""
        with self.lock:
            return sum(n for at, n in zip(self.done_at, self.images_done) if at <= t)

    def shed(self, ms: float, reason: str, tenant: str | None = None):
        """One shed response (already counted in err()): reason, answer
        latency, and the tenant it was shed FROM."""
        with self.lock:
            self.sheds_by_reason[reason] = (
                self.sheds_by_reason.get(reason, 0) + 1)
            self.shed_latencies_ms.append(ms)
            if tenant is not None:
                self._tenant(tenant)["shed"] += 1

    def err(self, msg: str | None = None, model: str | None = None,
            tenant: str | None = None):
        with self.lock:
            self.errors += 1
            self.err_at.append(time.perf_counter())
            if tenant is not None:
                self._tenant(tenant)["errors"] += 1
            if model is not None:
                m = self.per_model.setdefault(model, {"completed": 0, "errors": 0})
                m["errors"] += 1
            if msg and self.sample_error is None:
                self.sample_error = msg


def parse_model_mix(s: str | None) -> list[tuple[str, float]] | None:
    """``"a=3,b=1"`` (or bare ``"a,b"`` for equal weights) → [(name, w)...]
    for weighted per-request model routing against the multi-model server.
    Weights are relative; names may carry ``@version`` pins."""
    if not s:
        return None
    mix = []
    for part in s.split(","):
        part = part.strip()
        if not part:
            continue
        name, _, w = part.partition("=")
        try:
            weight = float(w) if w else 1.0
        except ValueError:
            raise ValueError(f"bad --model-mix weight in {part!r}") from None
        if weight <= 0:
            raise ValueError(f"--model-mix weight must be > 0 in {part!r}")
        mix.append((name.strip(), weight))
    if not mix:
        raise ValueError(f"empty --model-mix {s!r}")
    return mix


def parse_tenants(s: str | None) -> list[tuple[str, float]] | None:
    """``--tenants N[:W1,W2,...]`` → [(tenant, weight), ...]: N synthetic
    tenants named t0..t{N-1}, drawn per request (X-Tenant header).
    ``"3"`` gives equal weights; ``"3:8,1,1"`` skews the draw (t0 sends
    80% of traffic — the noisy-neighbor shape the server's per-tenant
    quotas exist for)."""
    if not s:
        return None
    n_s, _, w_s = s.partition(":")
    try:
        n = int(n_s)
    except ValueError:
        raise ValueError(f"bad --tenants count in {s!r}") from None
    if n <= 0:
        raise ValueError(f"--tenants count must be > 0, got {s!r}")
    if w_s:
        try:
            weights = [float(w) for w in w_s.split(",")]
        except ValueError:
            raise ValueError(f"bad --tenants weights in {s!r}") from None
        if len(weights) != n or any(w <= 0 for w in weights):
            raise ValueError(
                f"--tenants needs exactly {n} positive weights, got {s!r}")
    else:
        weights = [1.0] * n
    return [(f"t{i}", weights[i]) for i in range(n)]


def pick_tenant(rnd, tenants) -> str | None:
    """Weighted tenant draw from a parse_tenants list (None passes)."""
    if not tenants:
        return None
    return rnd.choices([t for t, _ in tenants],
                       weights=[w for _, w in tenants])[0]


def pick_model(rnd, mix) -> str | None:
    """Weighted draw from a parse_model_mix list (None passes through)."""
    if not mix:
        return None
    return rnd.choices([m for m, _ in mix], weights=[w for _, w in mix])[0]


def make_payload(images, rnd, files_per_request: int, weights=None,
                 labels=None):
    """(body, content_type, n_images[, size_label]): a raw JPEG body for
    1, or a multipart batch for N > 1 (the server's multi-image /predict —
    one HTTP round trip carries N images and returns {"results": [...]}).
    ``weights`` (e.g. :func:`zipf_weights`) skews the per-image draw —
    heavy-tailed key sampling over the corpus. ``labels`` (the --sizes
    corpus's parallel "WxH" list) rides along as a 4th element on
    single-image payloads so the Recorder can split latency per size;
    multipart bodies mix sizes, so they stay unlabeled."""
    if files_per_request <= 1:
        idx = (rnd.choices(range(len(images)), weights=weights)[0] if weights
               else rnd.randrange(len(images)))
        if labels:
            return images[idx], "image/jpeg", 1, labels[idx]
        return images[idx], "image/jpeg", 1
    if weights:
        chosen = rnd.choices(images, weights=weights, k=files_per_request)
    else:
        chosen = [rnd.choice(images) for _ in range(files_per_request)]
    # The boundary must not occur inside any payload (the parser splits on
    # the bare delimiter) — user-supplied images are arbitrary bytes.
    n = 0
    while True:
        boundary = f"loadgenboundary{n}"
        if all(b"--" + boundary.encode() not in c for c in chosen):
            break
        n += 1
    parts = b"".join(
        (
            f"--{boundary}\r\n"
            f'Content-Disposition: form-data; name="f{i}"; filename="{i}.jpg"\r\n\r\n'
        ).encode()
        + c
        + b"\r\n"
        for i, c in enumerate(chosen)
    )
    body = parts + f"--{boundary}--\r\n".encode()
    return body, f"multipart/form-data; boundary={boundary}", files_per_request


class HttpClient:
    """One persistent HTTP/1.1 connection with transparent reconnect.

    The server's worker-pool front end keeps connections alive across
    requests, so the client must reuse them for the bench to measure it —
    a fresh urllib connection per request re-pays the TCP handshake the
    server-side work removed. A request that fails at the connection level
    (stale keep-alive socket closed by the server's idle timeout) is
    retried once on a fresh connection; HTTP-level errors (4xx/5xx) are
    never retried.
    """

    def __init__(self, url: str, timeout: float, keepalive: bool = True):
        u = urllib.parse.urlsplit(url)
        if u.scheme and u.scheme != "http":
            # Refuse rather than silently speaking cleartext to an https://
            # target and reporting the resets as server errors.
            raise ValueError(f"only http:// URLs are supported, got {u.scheme}://")
        self.host = u.hostname or "127.0.0.1"
        self.port = u.port or 80
        self.path = (u.path or "/") + (f"?{u.query}" if u.query else "")
        self.timeout = timeout
        self.keepalive = keepalive
        self.conn: http.client.HTTPConnection | None = None
        self.last_trace_id: str | None = None  # X-Trace-Id of the last response
        self.last_cache: str | None = None  # X-Cache of the last response

    def _connect(self, rec: Recorder | None):
        conn = http.client.HTTPConnection(self.host, self.port, timeout=self.timeout)
        try:
            conn.connect()
        except Exception:
            # Leave self.conn unset: a half-built connection would make the
            # next post() skip _connect and let http.client auto-connect
            # behind the Recorder's back (undercounting connections).
            conn.close()
            raise
        self.conn = conn
        if rec is not None:
            rec.connected()

    def close(self):
        if self.conn is not None:
            try:
                self.conn.close()
            finally:
                self.conn = None

    def request_path(self, model: str | None = None) -> str:
        """The request target, optionally routed to one model of a
        multi-model server via the ``?model=`` query parameter."""
        if not model:
            return self.path
        sep = "&" if "?" in self.path else "?"
        return f"{self.path}{sep}model={urllib.parse.quote(model, safe='@')}"

    def post(self, body: bytes, ctype: str, rec: Recorder | None = None,
             path: str | None = None,
             extra_headers: dict | None = None) -> tuple[int, bytes]:
        headers = {"Content-Type": ctype}
        if extra_headers:
            # Overload headers (X-Tenant / X-SLO / X-Deadline-Ms) ride
            # here; Content-Type/Connection stay authoritative.
            headers.update(extra_headers)
        if not self.keepalive:
            headers["Connection"] = "close"
        for attempt in (0, 1):
            if self.conn is None:
                self._connect(rec)
            try:
                self.conn.request("POST", path or self.path, body=body,
                                  headers=headers)
                resp = self.conn.getresponse()
                data = resp.read()
                status = resp.status
                self.last_trace_id = resp.getheader("X-Trace-Id")
                self.last_cache = resp.getheader("X-Cache")
            except TimeoutError:
                # The request reached the server and the RESPONSE timed out:
                # an error, not a stale socket — a retry would double-send
                # the image and record a latency spanning both attempts.
                self.close()
                raise
            except (http.client.HTTPException, ConnectionError, BrokenPipeError, OSError):
                # Connection-level failure: retry ONCE on a fresh socket
                # (covers the server closing an idle kept-alive connection
                # between our send and its read).
                self.close()
                if attempt:
                    raise
                continue
            if not self.keepalive or resp.will_close:
                self.close()
            return status, data
        raise AssertionError("unreachable")


def one_request(url: str, payload: tuple, timeout: float, rec: Recorder,
                client: HttpClient | None = None, model: str | None = None,
                tenant: str | None = None,
                extra_headers: dict | None = None):
    """``payload`` is ``make_payload``'s (body, content_type, n_images).
    With ``client`` the request rides that persistent connection; without,
    a one-shot connection is opened (and counted) for it. ``model`` routes
    the request to that model of a multi-model server (``?model=``);
    ``tenant`` stamps X-Tenant (per-tenant quota accounting) and
    ``extra_headers`` carries X-SLO / X-Deadline-Ms opt-ins."""
    body, ctype, n = payload[:3]
    size_label = payload[3] if len(payload) > 3 else None
    own = client is None
    if own:
        client = HttpClient(url, timeout)
    path = client.request_path(model)
    headers = dict(extra_headers or {})
    if tenant is not None:
        headers["X-Tenant"] = tenant
    t0 = time.perf_counter()
    try:
        status, data = client.post(body, ctype, rec, path=path,
                                   extra_headers=headers or None)
        ms = (time.perf_counter() - t0) * 1e3
        if status == 200:
            rec.ok(ms, images=n, trace_id=client.last_trace_id,
                   model=model, cache=client.last_cache, tenant=tenant,
                   size=size_label)
        else:
            rec.err(f"HTTP {status}", model=model, tenant=tenant)
            if status in (429, 503, 504):
                # A shed with a machine-readable reason: count it by
                # reason + tenant and record how fast the rejection
                # itself was answered.
                reason = None
                try:
                    reason = json.loads(data).get("reason")
                except Exception:
                    pass
                rec.shed(ms, reason or f"http_{status}", tenant=tenant)
    except ConnectionRefusedError as e:
        rec.err(str(e), model=model, tenant=tenant)
        time.sleep(0.2)  # dead server: don't busy-loop the workers
    except Exception as e:
        rec.err(f"{type(e).__name__}: {e}", model=model, tenant=tenant)
    finally:
        if own:
            client.close()


def closed_loop(url, images, workers, duration, timeout, rec, files_per_request=1,
                keepalive=True, model_mix=None, weights=None, tenants=None,
                extra_headers=None, size_labels=None):
    """N workers, one in-flight request each; every worker owns ONE
    persistent connection for its whole run (the keep-alive operating
    point), or a fresh connection per request with ``keepalive=False``
    (the HTTP/1.0-era baseline, kept for comparison). ``model_mix`` (see
    :func:`parse_model_mix`) draws a model per request for mixed-model
    traffic against the registry server; ``weights`` (see
    :func:`zipf_weights`) skews the image draw heavy-tailed."""
    stop = time.perf_counter() + duration

    def worker(seed):
        rnd = random.Random(seed)
        # With keepalive=False the SAME client object sends Connection:
        # close and reconnects per request — the counted per-request
        # connections are the point of the baseline.
        client = HttpClient(url, timeout, keepalive=keepalive)
        try:
            while time.perf_counter() < stop:
                one_request(url,
                            make_payload(images, rnd, files_per_request,
                                         weights=weights,
                                         labels=size_labels),
                            timeout, rec, client=client,
                            model=pick_model(rnd, model_mix),
                            tenant=pick_tenant(rnd, tenants),
                            extra_headers=extra_headers)
        finally:
            client.close()

    threads = [threading.Thread(target=worker, args=(i,)) for i in range(workers)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()


class _ClientPool:
    """Checkout pool of persistent connections for open-loop arrivals:
    request threads come and go, connections stay warm."""

    def __init__(self, url, timeout):
        self.url, self.timeout = url, timeout
        self._lock = threading.Lock()
        self._idle: list[HttpClient] = []

    def get(self) -> HttpClient:
        with self._lock:
            if self._idle:
                return self._idle.pop()
        return HttpClient(self.url, self.timeout)

    def put(self, client: HttpClient):
        with self._lock:
            self._idle.append(client)


def open_loop(url, images, rate, duration, timeout, rec, max_threads=1024,
              files_per_request=1, keepalive=True, model_mix=None,
              weights=None, tenants=None, extra_headers=None,
              size_labels=None):
    """Poisson arrivals; each request gets its own thread so a slow server
    cannot slow the arrival process (no coordinated omission). Threads
    check persistent connections out of a shared pool so arrivals reuse
    sockets without serializing behind each other.

    Returns submit-loop health stats: ``submit_loop_utilization`` (fraction
    of the run the arrival dispatcher spent working rather than sleeping
    until the next scheduled arrival) and ``client_limited`` (True when the
    dispatcher could not keep the offered schedule — the measured numbers
    are then bounded by THIS process, not the server, and must not be
    reported as server capacity)."""
    rnd = random.Random(0)
    pool_conns = _ClientPool(url, timeout) if keepalive else None
    # Pre-built payload pool (batch mode only): multipart assembly is
    # O(request size) and must NOT run in the arrival dispatcher, or the
    # offered load silently sags below the requested rate (the coordinated
    # omission this mode exists to avoid). At 1 file/request make_payload
    # is already O(1), so keep sampling the full corpus per arrival.
    if files_per_request > 1:
        # Heavy-tailed sampling bakes into the pre-built payloads (each
        # multipart draws its images Zipf-skewed at build time).
        pool = [make_payload(images, rnd, files_per_request, weights=weights)
                for _ in range(32)]
        pool_weights = None
    elif size_labels:
        pool = [(img, "image/jpeg", 1, lab)
                for img, lab in zip(images, size_labels)]
        pool_weights = weights  # weighted draw per arrival
    else:
        pool = [(img, "image/jpeg", 1) for img in images]
        pool_weights = weights  # weighted draw per arrival

    def fire(payload, model, tenant):
        if pool_conns is None:
            client = HttpClient(url, timeout, keepalive=False)
            try:
                one_request(url, payload, timeout, rec, client=client, model=model,
                            tenant=tenant, extra_headers=extra_headers)
            finally:
                client.close()
            return
        client = pool_conns.get()
        try:
            one_request(url, payload, timeout, rec, client=client, model=model,
                        tenant=tenant, extra_headers=extra_headers)
        finally:
            pool_conns.put(client)

    t_start = time.perf_counter()
    stop = t_start + duration
    live: list[threading.Thread] = []
    next_t = t_start
    slept = 0.0
    arrivals = late_arrivals = thread_cap_drops = 0
    max_behind_s = 0.0
    while next_t < stop:
        delay = rnd.expovariate(rate)
        next_t += delay
        now = time.perf_counter()
        if next_t > now:
            time.sleep(next_t - now)
            slept += next_t - now
        else:
            # The dispatcher is behind its own arrival schedule: the
            # offered load is silently sagging below --rate.
            behind = now - next_t
            max_behind_s = max(max_behind_s, behind)
            if behind > 0.005:
                late_arrivals += 1
        arrivals += 1
        live = [t for t in live if t.is_alive()]
        if len(live) >= max_threads:
            rec.err()  # overload: count as failure rather than stalling arrivals
            thread_cap_drops += 1
            continue
        t = threading.Thread(
            target=fire,
            args=(rnd.choices(pool, weights=pool_weights)[0]
                  if pool_weights else rnd.choice(pool),
                  pick_model(rnd, model_mix),
                  pick_tenant(rnd, tenants)),
            daemon=True,  # stragglers must not hold the process open after the summary
        )
        t.start()
        live.append(t)
    wall = max(time.perf_counter() - t_start, 1e-9)
    utilization = min(1.0, max(0.0, 1.0 - slept / wall))
    deadline = time.perf_counter() + timeout
    for t in live:
        t.join(timeout=max(0.0, deadline - time.perf_counter()))
    # Client-limited when the dispatcher had essentially no idle time, fell
    # behind schedule on a meaningful share of arrivals, or shed at the
    # thread cap — any of which means the client, not the server, set the
    # measured rate.
    client_limited = bool(
        utilization > 0.95
        or (arrivals and late_arrivals / arrivals > 0.1)
        or thread_cap_drops
    )
    return {
        "submit_loop_utilization": round(utilization, 3),
        "arrivals": arrivals,
        "late_arrivals": late_arrivals,
        "max_behind_ms": round(max_behind_s * 1e3, 1),
        "thread_cap_drops": thread_cap_drops,
        "client_limited": client_limited,
    }


def sweep_curve(url, images, rates_rps, step_s, timeout, files_per_request=1,
                keepalive=True, model_mix=None, weights=None,
                tenants=None, extra_headers=None,
                settle_s: float = 1.0) -> list[dict]:
    """Offered-load sweep: one open-loop window per rate in ``rates_rps``
    (requests/s), stepping PAST saturation, returning one row per step —
    offered vs goodput (completed images/s inside the window), p50/p99,
    errors (incl. 503 fast-rejects), and the client-limited flag. The
    ROADMAP item 1 curve: the number that proves the system bends (goodput
    plateaus at capacity while offered keeps climbing) instead of breaking
    (goodput collapsing under its own backlog). Shared by the CLI's
    ``--sweep`` mode and bench.py's ``overload`` block — one definition of
    how the curve is measured."""
    steps = []
    for rate in rates_rps:
        rec = Recorder()
        t0 = time.perf_counter()
        loop = open_loop(url, images, rate, step_s, timeout, rec,
                         files_per_request=files_per_request,
                         keepalive=keepalive, model_mix=model_mix,
                         weights=weights, tenants=tenants,
                         extra_headers=extra_headers)
        goodput = rec.images_completed_by(t0 + step_s) / step_s
        with rec.lock:
            lat = sorted(rec.latencies_ms)
            errors = rec.errors
            completed = len(rec.latencies_ms)
            sheds = sum(rec.sheds_by_reason.values())
            shed_lat = sorted(rec.shed_latencies_ms)
        offered_ips = rate * files_per_request
        steps.append({
            "offered_rps": round(rate, 2),
            "offered_images_per_sec": round(offered_ips, 1),
            "goodput_images_per_sec": round(goodput, 1),
            "goodput_fraction": round(goodput / offered_ips, 3)
            if offered_ips else None,
            "completed": completed,
            "errors": errors,
            "p50_ms": round(percentile(lat, 50), 1) if lat else None,
            "p99_ms": round(percentile(lat, 99), 1) if lat else None,
            # Shed answers are a SUBSET of errors (already counted there):
            # requests the server refused with a machine-readable reason
            # (429/503/504). Their answer latency proves sheds are cheap —
            # a shed that takes as long as an inference is no protection.
            "sheds": sheds,
            "shed_answer_p99_ms": round(percentile(shed_lat, 99), 1)
            if shed_lat else None,
            "client_limited": loop["client_limited"],
        })
        # Drain pause between steps so one step's backlog doesn't bleed
        # into the next step's latency percentiles.
        time.sleep(settle_s)
    return steps


def format_sweep_table(steps: list[dict]) -> str:
    """Human-readable offered-vs-goodput table (stderr; stdout stays one
    JSON line)."""
    if not steps:
        return "(no sweep steps)"
    rows = [f"{'offered/s':>10} {'goodput/s':>10} {'good%':>6} "
            f"{'p50 ms':>8} {'p99 ms':>9} {'errors':>7}"]
    for s in steps:
        frac = s["goodput_fraction"]
        rows.append(
            f"{s['offered_images_per_sec']:>10.1f} "
            f"{s['goodput_images_per_sec']:>10.1f} "
            f"{(frac * 100 if frac is not None else 0):>5.0f}% "
            f"{s['p50_ms'] if s['p50_ms'] is not None else '-':>8} "
            f"{s['p99_ms'] if s['p99_ms'] is not None else '-':>9} "
            f"{s['errors']:>7}"
            + ("  CLIENT-LIMITED" if s["client_limited"] else "")
        )
    return "\n".join(rows)


def sweep_summary(steps: list[dict]) -> dict:
    """Saturation analysis over sweep steps: peak goodput, the knee (last
    offered rate the server still served ≥90% of), and whether goodput
    held up (≥80% of its peak) at the highest offered load — "bends, not
    breaks" as a boolean."""
    if not steps:
        return {}
    peak = max(s["goodput_images_per_sec"] for s in steps)
    # Knee = the HIGHEST offered rate still served ≥90% (max, not last:
    # an explicit --sweep rate list may arrive unsorted).
    served = [s["offered_images_per_sec"] for s in steps
              if s["goodput_fraction"] is not None
              and s["goodput_fraction"] >= 0.9]
    knee = max(served) if served else None
    last = max(steps, key=lambda s: s["offered_images_per_sec"])
    return {
        "peak_goodput_images_per_sec": peak,
        "knee_offered_images_per_sec": knee,
        "goodput_at_max_offered": last["goodput_images_per_sec"],
        "degrades_gracefully": bool(
            peak > 0 and last["goodput_images_per_sec"] >= 0.8 * peak
        ),
    }


def run_sweep(args, images, weights, mix, fpr, ka, tenants=None,
              extra_headers=None) -> int:
    """``--sweep`` mode: step offered load past saturation and print the
    offered-load vs goodput (and p99) table. ``--sweep auto`` calibrates
    capacity with a short closed-loop probe and steps 0.5×..2× around it;
    an explicit ``--sweep R1,R2,...`` sweeps those request rates."""
    step_s = args.sweep_step_s or min(args.duration, 8.0)
    if args.sweep.strip().lower() == "auto":
        probe_s = min(5.0, step_s)
        rec_c = Recorder()
        t0 = time.perf_counter()
        closed_loop(args.url, images, args.workers, probe_s, args.timeout,
                    rec_c, files_per_request=fpr, keepalive=ka,
                    model_mix=mix, weights=weights, tenants=tenants,
                    extra_headers=extra_headers)
        base_rps = rec_c.images_completed_by(t0 + probe_s) / probe_s / fpr
        if base_rps <= 0:
            print("sweep calibration failed: no completed requests",
                  file=sys.stderr)
            return 1
        rates = [max(0.5, base_rps * f)
                 for f in (0.5, 0.75, 1.0, 1.25, 1.5, 2.0)]
        print(f"sweep: calibrated capacity ≈{base_rps * fpr:.1f} img/s "
              f"closed-loop; stepping 0.5×..2×", file=sys.stderr)
    else:
        try:
            rates = [float(r) for r in args.sweep.split(",") if r.strip()]
        except ValueError:
            sys.exit(f"--sweep must be 'auto' or comma-separated "
                     f"request rates, got {args.sweep!r}")
        if not rates:
            sys.exit("--sweep: no rates given")
    steps = sweep_curve(args.url, images, rates, step_s, args.timeout,
                        files_per_request=fpr, keepalive=ka, model_mix=mix,
                        weights=weights, tenants=tenants,
                        extra_headers=extra_headers)
    print(format_sweep_table(steps), file=sys.stderr)
    summary = {
        "mode": f"sweep({len(steps)} steps × {step_s:g}s)",
        "step_s": step_s,
        "files_per_request": fpr,
        "steps": steps,
        **sweep_summary(steps),
    }
    print(json.dumps(summary))
    return 0 if any(s["completed"] for s in steps) else 1


def format_econ_table(econ: dict | None) -> str:
    """Human-readable roofline table from a server's /stats "economics"
    block: per (model, replica, canvas, batch-bucket) cell — MFU,
    arithmetic intensity, the binding roofline side and achieved fraction
    of it, and the padding-waste fractions. Shared by bench.py and
    tools/profile_serve.py so both tools render the SAME live numbers."""
    if not econ:
        return "(no economics block — engine without econ counters?)"
    lines = []
    for ref, e in econ.items():
        head = [ref]
        mc = e.get("model_cost")
        if mc:
            head.append(f"{mc['flops_per_image'] / 1e9:.2f} GFLOP/img")
            head.append(f"{mc['param_bytes'] / 1e6:.1f} MB params")
        peak = e.get("peak")
        if peak:
            head.append(
                f"peak {peak['flops_per_chip'] / 1e12:.3f} TFLOP/s/chip "
                f"({peak['source']})"
            )
        if e.get("mfu") is not None:
            head.append(f"MFU {e['mfu']:.2%}")
        if e.get("padded_rows_fraction") is not None:
            head.append(f"padded rows {e['padded_rows_fraction']:.1%}")
        lines.append("  ".join(head))
        pad_by = {
            (p["canvas"], p["batch_bucket"]): p
            for p in (e.get("padding") or {}).values()
        }
        cells = [
            (rep, c)
            for rep in e.get("replicas", [])
            for c in rep.get("buckets", [])
        ]
        if cells:
            lines.append(
                f"  {'repl':>4} {'canvas':>6} {'batch':>5} {'mfu':>7} "
                f"{'AI':>7} {'bound':>9} {'of-roof':>7} {'padrow':>6} "
                f"{'padpx':>6} {'dev_s':>8}"
            )
        for rep, c in cells:
            p = pad_by.get((c["canvas"], c["batch_bucket"]), {})
            mfu = c.get("mfu")
            ai = c.get("arithmetic_intensity")
            bf = c.get("roofline_bound_fraction")
            padpx = p.get("padded_px_fraction")
            mfu_s = "-" if mfu is None else f"{mfu:.2%}"
            ai_s = "-" if ai is None else f"{ai:.1f}"
            bf_s = "-" if bf is None else f"{bf:.1%}"
            padpx_s = "-" if padpx is None else f"{padpx:.1%}"
            lines.append(
                f"  {rep['replica']:>4} {c['canvas']:>6} "
                f"{c['batch_bucket']:>5} {mfu_s:>7} {ai_s:>7} "
                f"{c.get('bound', '-'):>9} {bf_s:>7} "
                f"{c['padded_rows_fraction']:>6.1%} {padpx_s:>6} "
                f"{c['device_s']:>8.2f}"
            )
    return "\n".join(lines)


def fetch_stats(url: str, timeout: float = 5.0) -> dict | None:
    """GET the server's full ``/stats`` document (host derived from the
    target URL), or None when the server is unreachable or isn't ours
    (fail-soft: the client-side summary must never depend on server
    cooperation)."""
    u = urllib.parse.urlsplit(url)
    stats_url = f"http://{u.hostname or '127.0.0.1'}:{u.port or 80}/stats"
    try:
        with urllib.request.urlopen(stats_url, timeout=timeout) as r:
            return json.load(r)
    except Exception:
        return None


def _history_base(url: str) -> str:
    u = urllib.parse.urlsplit(url)
    return f"http://{u.hostname or '127.0.0.1'}:{u.port or 80}/debug/history"


def fetch_history(url: str, series: list[str], last_s: float, res: str,
                  timeout: float = 5.0) -> dict | None:
    """GET a bounded window of named series from the server's telemetry
    rings (host derived from the target URL), or None when unreachable or
    telemetry is disabled (fail-soft, like fetch_stats)."""
    q = urllib.parse.urlencode({
        "series": ",".join(series),
        "last_s": f"{last_s:g}",
        "res": res,
    })
    try:
        with urllib.request.urlopen(f"{_history_base(url)}?{q}",
                                    timeout=timeout) as r:
            return json.load(r)
    except Exception:
        return None


class HistoryPoller:
    """Polls ``/debug/history`` during the timed window and merges the
    returned buckets by timestamp, so the timeline survives runs longer
    than the finest ring's retention and duplicate buckets across polls
    collapse. Gives the run a *server-side* per-step view (goodput, p99,
    busy fraction) next to the client-side summary — the two disagree
    exactly when the client is the bottleneck.

    All fetches are fail-soft: a dead or telemetry-less server just
    yields an empty table, never a loadgen error.
    """

    SERIES = ("goodput_rps", "e2e_p99_ms")

    def __init__(self, url: str, duration_s: float, timeout: float = 5.0):
        self.url = url
        self.timeout = min(timeout, 5.0)
        # 1 s buckets read cleanly up to the 5 min ring; longer runs drop
        # to the 10 s ring so one poll still covers the poll interval.
        self.res = "1s" if duration_s <= 240 else "10s"
        self.poll_s = max(2.0, min(30.0, duration_s / 4.0))
        self.buckets: dict[str, dict[float, list]] = {}
        self.available: list[str] | None = None
        self.busy_series: list[str] = []
        self._stop = threading.Event()
        self._thread = threading.Thread(target=self._run,
                                        name="history-poller", daemon=True)

    def start(self) -> None:
        self._thread.start()

    def stop(self) -> None:
        self._stop.set()
        self._thread.join(timeout=self.timeout + 5.0)
        self._poll_once()  # final poll picks up the window's tail

    def _run(self) -> None:
        while not self._stop.wait(self.poll_s):
            self._poll_once()

    def _poll_once(self) -> None:
        if self.available is None:
            # First contact: the catalog response (no series param) tells
            # us which replica busy-fraction series exist on this server.
            try:
                with urllib.request.urlopen(_history_base(self.url),
                                            timeout=self.timeout) as r:
                    cat = json.load(r)
            except Exception:
                return
            self.available = list(cat.get("series") or ())
            self.busy_series = sorted(
                s for s in self.available
                if s.startswith("replica.busy_fraction."))[:8]
        want = [s for s in self.SERIES if s in self.available]
        want += self.busy_series
        if not want:
            return
        # Overlap consecutive polls (2× the interval) so a slow poll never
        # leaves a gap; the bucket merge dedups the overlap.
        doc = fetch_history(self.url, want,
                            last_s=min(2 * self.poll_s + 5.0, 300.0),
                            res=self.res, timeout=self.timeout)
        if not doc:
            return
        for name, sd in (doc.get("series") or {}).items():
            dst = self.buckets.setdefault(name, {})
            for row in sd.get("rows", ()):
                dst[row[0]] = row

    def timeline(self, max_rows: int = 24) -> list[dict]:
        """Merged per-bucket rows (oldest first), strided down to at most
        ``max_rows``. Columns follow /debug/history: each bucket is
        [t, min, mean, max, last, count]."""
        goodput = self.buckets.get("goodput_rps", {})
        p99 = self.buckets.get("e2e_p99_ms", {})
        busy = [self.buckets.get(s, {}) for s in self.busy_series]
        ts = set(goodput) | set(p99)
        for b in busy:
            ts |= set(b)
        ts_sorted = sorted(ts)
        if not ts_sorted:
            return []
        stride = max(1, -(-len(ts_sorted) // max_rows))
        t0 = ts_sorted[0]
        out = []
        for t in ts_sorted[::stride]:
            fracs = [b[t][2] for b in busy if t in b]
            out.append({
                "t_s": round(t - t0, 1),
                "goodput_rps": (round(goodput[t][2], 1)
                                if t in goodput else None),
                # max, not mean: a one-bucket latency spike must survive
                # into the table the way it survives in the ring.
                "p99_ms": round(p99[t][3], 1) if t in p99 else None,
                "busy_fraction": (round(sum(fracs) / len(fracs), 3)
                                  if fracs else None),
            })
        return out

    def table(self, rows: list[dict]) -> str:
        lines = [f"  {'t(s)':>6} {'goodput/s':>10} {'p99(ms)':>9} "
                 f"{'busy':>6}"]
        for r in rows:
            def fmt(v, spec):
                return format(v, spec) if v is not None else "-"
            lines.append(
                f"  {r['t_s']:>6.1f} {fmt(r['goodput_rps'], '.1f'):>10} "
                f"{fmt(r['p99_ms'], '.1f'):>9} "
                f"{fmt(r['busy_fraction'], '.0%'):>6}")
        return "\n".join(lines)


def mean_batch_size(stats: dict | None) -> float:
    """Rolling mean dispatched batch size from a ``/stats`` snapshot's
    ``batch_size_histogram`` (≥1.0; 1.0 when unknown). Needed to de-bias
    span-based device utilization: every request in a batch stamps the
    whole batch's ``device_execute`` interval, so summed span time
    overcounts true device busy-time by the mean batch size."""
    hist = (stats or {}).get("batch_size_histogram") or {}
    total = sum(hist.values())
    if not total:
        return 1.0
    return max(1.0, sum(int(size) * n for size, n in hist.items()) / total)


def stage_attribution(before: dict | None, after: dict | None) -> dict:
    """Diff two ``/stats`` tracing snapshots into per-stage count /
    total_ms / mean_ms over the window between them. The server's stage
    counters are cumulative (histogram sums never reset), so the diff is
    exact regardless of other traffic before the run; ``before=None``
    attributes everything since server start. The end-to-end aggregate
    rides along under the reserved key ``_e2e``."""
    if not after:
        return {}
    out = {}
    b_stages = (before or {}).get("stages", {})
    for name, s in after.get("stages", {}).items():
        prev = b_stages.get(name, {})
        c = s.get("count", 0) - prev.get("count", 0)
        t = s.get("total_ms", 0.0) - prev.get("total_ms", 0.0)
        if c > 0:
            out[name] = {"count": c, "total_ms": round(t, 3),
                         "mean_ms": round(t / c, 3)}
    eb = (before or {}).get("e2e", {})
    ea = after.get("e2e", {})
    ec = ea.get("count", 0) - eb.get("count", 0)
    et = ea.get("total_ms", 0.0) - eb.get("total_ms", 0.0)
    if ec > 0:
        out["_e2e"] = {"count": ec, "total_ms": round(et, 3),
                       "mean_ms": round(et / ec, 3)}
    return out


def format_stage_table(attr: dict, wall_s: float | None = None) -> str:
    """Stage-attribution table: where server-side request time went, by
    stage, with each stage's share of end-to-end time. Stages from cheap
    monitoring GETs (http_read/body_read on /stats itself) are included —
    the decode/queue/device rows can only come from /predict traffic.

    With ``wall_s`` (the measurement window) each row also shows its
    UTILIZATION — stage span-time ÷ wall clock. Parallel stages (decode
    across HTTP workers) legitimately exceed 100%, and batch-shared
    stages (``device_execute``/``device_transfer``) overcount true busy
    time by the mean batch size (every request in a batch stamps the
    whole batch's interval) — divide by :func:`mean_batch_size` for the
    de-biased device figure, as the closed-loop client-limited check
    does."""
    if not attr:
        return "(no server-side stage data)"
    e2e = attr.get("_e2e")
    hdr = f"{'stage':<16} {'count':>8} {'mean_ms':>9} {'total_ms':>11}"
    hdr += "  share" if e2e else ""
    hdr += "   util" if wall_s else ""
    lines = [hdr]
    stages = sorted(
        ((k, v) for k, v in attr.items() if k != "_e2e"),
        key=lambda kv: -kv[1]["total_ms"],
    )
    for name, s in stages:
        row = f"{name:<16} {s['count']:>8} {s['mean_ms']:>9.2f} {s['total_ms']:>11.1f}"
        if e2e and e2e["total_ms"] > 0:
            row += f"  {100.0 * s['total_ms'] / e2e['total_ms']:5.1f}%"
        if wall_s:
            row += f"  {100.0 * s['total_ms'] / 1e3 / wall_s:5.1f}%"
        lines.append(row)
    if e2e:
        lines.append(
            f"{'(end-to-end)':<16} {e2e['count']:>8} {e2e['mean_ms']:>9.2f} "
            f"{e2e['total_ms']:>11.1f}"
        )
    return "\n".join(lines)


def stage_utilization(attr: dict, wall_s: float) -> dict:
    """Per-stage busy fraction of the measurement window (total_ms/wall).
    The machine-readable twin of the table's util column; >1.0 means the
    stage ran concurrently with itself across workers/batches."""
    if not attr or not wall_s or wall_s <= 0:
        return {}
    return {
        name: round(s["total_ms"] / 1e3 / wall_s, 3)
        for name, s in attr.items() if name != "_e2e"
    }


def replica_utilization(stats_before: dict | None, stats_after: dict | None,
                        wall_s: float) -> list[dict]:
    """Per-chip busy fractions from the default model's ``/stats``
    "staging" replicas block (placement routing): each replica's
    device-phase ``busy_s`` delta over the window ÷ wall, capped at 1.0
    (the two reads need not fall where a call's phase does). Empty for
    single-stream placements —
    there is nothing to disperse."""
    after = ((stats_after or {}).get("staging") or {}).get("replicas") or []
    if len(after) < 2 or not wall_s or wall_s <= 0:
        return []
    before = {
        r.get("replica"): r
        for r in (((stats_before or {}).get("staging") or {}).get("replicas")
                  or [])
    }
    out = []
    for r in after:
        prev = before.get(r.get("replica"), {})
        busy = r.get("busy_s", 0.0) - prev.get("busy_s", 0.0)
        disp = r.get("dispatches_total", 0) - prev.get("dispatches_total", 0)
        out.append({
            "replica": r.get("replica"),
            "devices": r.get("devices"),
            "dispatches": disp,
            "busy_fraction": round(min(1.0, max(0.0, busy) / wall_s), 3),
        })
    return out


def _job_base_url(url: str) -> str:
    u = urllib.parse.urlsplit(url)
    return f"http://{u.hostname or '127.0.0.1'}:{u.port or 80}"


def _http_json(method: str, url: str, body: bytes | None = None,
               ctype: str = "application/json", timeout: float = 30.0):
    """One request → (status, parsed JSON or None, headers dict)."""
    req = urllib.request.Request(url, data=body, method=method)
    if body is not None:
        req.add_header("Content-Type", ctype)
    try:
        with urllib.request.urlopen(req, timeout=timeout) as r:
            data = r.read()
            return r.status, (json.loads(data) if data else None), dict(r.headers)
    except urllib.error.HTTPError as e:
        data = e.read()
        try:
            doc = json.loads(data) if data else None
        except ValueError:
            doc = {"error": data[:200].decode("utf-8", "replace")}
        return e.code, doc, dict(e.headers or {})


def _job_multipart(files: list[tuple[str, bytes]]) -> tuple[bytes, str]:
    """Multipart body carrying EVERY file, in order (make_payload samples
    randomly — a job manifest must be exact)."""
    n = 0
    while True:
        boundary = f"loadgenjob{n}"
        if all(b"--" + boundary.encode() not in c for _, c in files):
            break
        n += 1
    parts = b"".join(
        (
            f"--{boundary}\r\n"
            f'Content-Disposition: form-data; name="f{i}"; filename="{name}"\r\n\r\n'
        ).encode()
        + data
        + b"\r\n"
        for i, (name, data) in enumerate(files)
    )
    return (parts + f"--{boundary}--\r\n".encode(),
            f"multipart/form-data; boundary={boundary}")


def _interactive_phase(url, images, workers, seconds_or_stop, timeout,
                       weights=None):
    """Stoppable closed-loop interactive load: ``seconds_or_stop`` is a
    float (run that long) or a threading.Event (run until set). Returns
    the Recorder — the same measurement for the baseline and the
    with-job phases, so the p99 comparison is apples-to-apples."""
    rec = Recorder()
    ev = (seconds_or_stop if isinstance(seconds_or_stop, threading.Event)
          else None)
    stop_at = (None if ev is not None
               else time.perf_counter() + float(seconds_or_stop))

    def worker(seed):
        rnd = random.Random(seed)
        client = HttpClient(url, timeout)
        try:
            while ((ev is None or not ev.is_set())
                   and (stop_at is None or time.perf_counter() < stop_at)):
                one_request(url, make_payload(images, rnd, 1, weights=weights),
                            timeout, rec, client=client)
        finally:
            client.close()

    threads = [threading.Thread(target=worker, args=(i,), daemon=True)
               for i in range(workers)]
    for t in threads:
        t.start()
    if ev is None:
        for t in threads:
            t.join()
        return rec, None
    return rec, threads


def run_job_mode(args, images, weights) -> int:
    """``--job FILE_OR_DIR``: submit a bulk job, poll its progress, stream
    its results (offset-resumable), and report job img/s next to the
    interactive tier's p50/p99 measured WITHOUT and WITH the job running
    — the isolation number the bulk traffic class exists for."""
    base = _job_base_url(args.url)
    predict_url = f"{base}/predict"
    src = Path(args.job)
    if not src.exists():
        sys.exit(f"--job: no such file or directory: {args.job}")

    # Phase 1 — interactive baseline (no job running).
    print(f"job mode: measuring interactive baseline for {args.duration:.0f}s",
          file=sys.stderr)
    rec_base, _ = _interactive_phase(predict_url, images, args.workers,
                                     args.duration, args.timeout,
                                     weights=weights)
    with rec_base.lock:
        base_lat = sorted(rec_base.latencies_ms)
        base_n = len(base_lat)

    # Phase 2 — submit the job.
    qs = []
    if args.job_topk is not None:
        qs.append(f"topk={args.job_topk}")
    if args.job_model:
        qs.append(f"model={urllib.parse.quote(args.job_model, safe='')}")
    suffix = ("?" + "&".join(qs)) if qs else ""
    if args.job_server_dir:
        body = json.dumps({"dir": str(src.resolve())}).encode()
        status, doc, _ = _http_json("POST", f"{base}/jobs{suffix}", body)
    else:
        paths = (sorted(p for p in src.iterdir() if p.is_file())
                 if src.is_dir() else [src])
        files = [(p.name, p.read_bytes()) for p in paths]
        mp_body, mp_ctype = _job_multipart(files)
        status, doc, _ = _http_json("POST", f"{base}/jobs{suffix}", mp_body,
                                    ctype=mp_ctype,
                                    timeout=max(args.timeout, 120.0))
    if status != 202:
        sys.exit(f"job submit failed: HTTP {status}: {doc}")
    job_id = doc["id"]
    total = doc["total"]
    print(f"job {job_id} accepted: {total} images", file=sys.stderr)

    # Phase 3 — interactive load runs WHILE the job does; poll + stream.
    stop = threading.Event()
    rec_during, threads = _interactive_phase(predict_url, images,
                                             args.workers, stop,
                                             args.timeout, weights=weights)
    t0 = time.perf_counter()
    offset = 0
    streamed = 0
    state = doc["state"]
    deadline = t0 + args.job_max_wait
    try:
        while time.perf_counter() < deadline:
            # Stream whatever results landed since the last poll — the
            # offset-resume protocol a real consumer uses. A transient
            # failure (500 under load, reset mid-long-poll) retries the
            # poll; the offset makes re-polling idempotent.
            req = urllib.request.Request(
                f"{base}/jobs/{job_id}/results?offset={offset}"
                f"&limit=5000&wait_s=0.5")
            try:
                with urllib.request.urlopen(req, timeout=args.timeout) as r:
                    payload = r.read()
                    state = r.headers.get("X-Job-State", state)
                    offset = int(r.headers.get("X-Job-Next-Offset", offset))
                    if payload:
                        streamed += payload.count(b"\n")
                    if (r.headers.get("X-Job-Complete") == "1"
                            and state in ("DONE", "FAILED", "CANCELLED")):
                        break
            except (urllib.error.URLError, OSError) as e:
                print(f"job poll retry: {e}", file=sys.stderr)
                time.sleep(0.5)
    finally:
        job_wall = time.perf_counter() - t0
        stop.set()
        for t in threads or ():
            t.join(timeout=args.timeout)

    status, final, _ = _http_json("GET", f"{base}/jobs/{job_id}")
    final = final or {}
    with rec_during.lock:
        dur_lat = sorted(rec_during.latencies_ms)

    def r1(v):
        return None if v is None else round(v, 1)

    completed = final.get("completed", 0)
    summary = {
        "mode": ("job+interactive" if args.workers else "job"),
        "job": {
            "id": job_id,
            "state": final.get("state", state),
            "total": total,
            "completed": completed,
            "cached": final.get("cached"),
            "errors": final.get("errors"),
            "versions": final.get("versions"),
            "wall_s": round(job_wall, 2),
            "images_per_sec": round(completed / job_wall, 2) if job_wall else None,
            "result_lines_streamed": streamed,
        },
        "interactive_baseline": {
            "requests": base_n,
            "images_per_sec": round(base_n / args.duration, 2),
            "latency_ms": {"p50": r1(percentile(base_lat, 50)),
                           "p99": r1(percentile(base_lat, 99))},
            "errors": rec_base.errors,
        },
        "interactive_with_job": {
            "requests": len(dur_lat),
            "images_per_sec": (round(len(dur_lat) / job_wall, 2)
                               if job_wall else None),
            "latency_ms": {"p50": r1(percentile(dur_lat, 50)),
                           "p99": r1(percentile(dur_lat, 99))},
            "errors": rec_during.errors,
        },
    }
    p99_a = percentile(base_lat, 99)
    p99_b = percentile(dur_lat, 99)
    if p99_a and p99_b:
        # THE isolation number: how much a running bulk job stretches the
        # interactive tail (the bulk gate's acceptance bound is < 2×).
        summary["interactive_p99_degradation"] = round(p99_b / p99_a, 2)
    print(json.dumps(summary))
    return 0 if final.get("state") == "DONE" else 1


def percentile(sorted_ms: list[float], q: float) -> float | None:
    """q-th percentile of an ascending list; None when empty (NaN is not
    representable in strict JSON)."""
    if not sorted_ms:
        return None
    i = min(len(sorted_ms) - 1, int(round(q / 100 * (len(sorted_ms) - 1))))
    return sorted_ms[i]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--url", default="http://127.0.0.1:8500/predict")
    ap.add_argument("--images", default=None, help="directory of jpeg/png files")
    ap.add_argument("--workers", type=int, default=16, help="closed-loop concurrency")
    ap.add_argument("--rate", type=float, default=None, help="open-loop arrivals/sec")
    ap.add_argument(
        "--files-per-request", type=int, default=1,
        help="images per request (>1 uses the multipart batch endpoint)",
    )
    ap.add_argument(
        "--zipf", type=float, default=None, metavar="S",
        help="heavy-tailed image-key sampling: draw each image Zipf(S)-"
             "skewed over the corpus (rank i gets weight 1/(i+1)^S; hot "
             "keys dominate at S≈1.1) — the workload the server's "
             "content-addressed response cache exists for. The summary "
             "gains hit-rate and per-hit/per-miss latency columns from "
             "the X-Cache response headers",
    )
    ap.add_argument(
        "--corpus", type=int, default=None,
        help="synthetic corpus size when --images is not given "
             "(default 8; 64 under --zipf so the distribution has a tail)",
    )
    ap.add_argument(
        "--sizes", default=None, metavar="WxH[:W],...",
        help="weighted mixed-size synthetic corpus, e.g. "
             "'200x150:3,640x480:1' for a 75/25 small/large upload mix — "
             "the traffic shape the server's ragged packing targets. The "
             "summary gains a per-size p50/p99 block. Mutually exclusive "
             "with --images and --zipf",
    )
    ap.add_argument(
        "--model-mix", default=None, metavar="NAME=W,...",
        help="weighted mixed-model traffic against the multi-model server: "
             "each request draws a model (e.g. 'resnet50=3,mobilenet_v2=1'; "
             "bare names = equal weights; names may pin '@version') and is "
             "routed via /predict?model=<draw>",
    )
    ap.add_argument(
        "--job", default=None, metavar="FILE_OR_DIR",
        help="bulk-job mode: submit FILE_OR_DIR to POST /jobs (multipart "
             "upload; --job-server-dir sends the path instead), poll "
             "progress, stream results with offset resume, and report job "
             "img/s next to the interactive p50/p99 measured with and "
             "without the job running — the isolation number",
    )
    ap.add_argument("--job-server-dir", action="store_true",
                    help="with --job DIR: register the directory server-side "
                         "instead of uploading the files")
    ap.add_argument("--job-model", default=None,
                    help="model NAME the job runs against (default: the "
                         "server's default model)")
    ap.add_argument("--job-topk", type=int, default=None,
                    help="top-k for the job's results")
    ap.add_argument("--job-max-wait", type=float, default=600.0,
                    help="seconds to wait for the job before giving up")
    ap.add_argument(
        "--sweep", default=None, metavar="RATES|auto",
        help="overload sweep: step offered load through the given "
             "request rates (comma-separated, requests/s) — or 'auto' to "
             "calibrate capacity closed-loop and step 0.5×..2× past "
             "saturation — and print the offered-load vs goodput (and "
             "p99) table. Each step is one open-loop window of "
             "--sweep-step-s seconds",
    )
    ap.add_argument("--sweep-step-s", type=float, default=None,
                    help="seconds per sweep step (default: min(duration, 8))")
    ap.add_argument("--duration", type=float, default=30.0, help="seconds of load")
    ap.add_argument("--warmup", type=float, default=3.0, help="untimed warmup seconds")
    ap.add_argument("--timeout", type=float, default=60.0)
    ap.add_argument("--no-keepalive", action="store_true",
                    help="open a fresh connection per request (measures the "
                         "handshake tax keep-alive removes)")
    ap.add_argument("--no-server-stats", action="store_true",
                    help="skip fetching the server's /stats tracing block "
                         "(per-stage attribution table) around the run")
    ap.add_argument(
        "--tenants", default=None, metavar="N[:W1,...,WN]",
        help="multi-tenant traffic: each request draws a tenant t0..tN-1 "
             "(weighted when ':W1,...,WN' is given, e.g. '2:4,1' for a "
             "noisy neighbor at 4× the victim's rate) and sends it as "
             "X-Tenant, so the server's per-tenant quotas apply. The "
             "summary gains per-tenant admit/shed rates and p99",
    )
    ap.add_argument(
        "--slo", default=None, metavar="CLASS",
        help="send X-SLO: CLASS (e.g. 'interactive') on every request — "
             "opts requests into the server's deadline enforcement at that "
             "class's default deadline",
    )
    ap.add_argument(
        "--deadline-ms", type=int, default=None, metavar="MS",
        help="send X-Deadline-Ms: MS on every request — an explicit "
             "per-request deadline; the server sheds 504 instead of "
             "serving late",
    )
    ap.add_argument(
        "--history", action="store_true",
        help="poll the server's /debug/history telemetry rings during the "
             "run and print a server-side timeline table (goodput, p99, "
             "busy fraction per step) next to the client summary; the "
             "summary JSON gains a 'server_timeline' block. No-op when "
             "the server runs --telemetry-interval 0",
    )
    args = ap.parse_args(argv)

    try:
        sizes = parse_sizes(args.sizes)
    except ValueError as e:
        sys.exit(str(e))
    size_labels = None
    if sizes:
        if args.images or args.zipf:
            sys.exit("--sizes builds its own weighted synthetic corpus; "
                     "it cannot combine with --images or --zipf")
        per = max(1, (args.corpus or 4 * len(sizes)) // len(sizes))
        images, size_labels, weights = synthetic_jpegs_sized(sizes,
                                                             per_size=per)
    else:
        images = load_images(args.images,
                             n=args.corpus or (64 if args.zipf else 8))
        weights = zipf_weights(len(images), args.zipf) if args.zipf else None
    if args.job:
        return run_job_mode(args, images, weights)
    fpr = max(1, args.files_per_request)
    ka = not args.no_keepalive
    try:
        mix = parse_model_mix(args.model_mix)
    except ValueError as e:
        sys.exit(str(e))
    try:
        tenants = parse_tenants(args.tenants)
    except ValueError as e:
        sys.exit(str(e))
    extra_headers = {}
    if args.slo:
        extra_headers["X-SLO"] = args.slo
    if args.deadline_ms is not None:
        extra_headers["X-Deadline-Ms"] = str(args.deadline_ms)
    extra_headers = extra_headers or None
    if args.sweep:
        if args.warmup > 0:
            # Warmup stays tenant-free: warming must not spend any
            # tenant's quota tokens before the measured window.
            closed_loop(args.url, images, 2, args.warmup, args.timeout,
                        Recorder(), files_per_request=fpr, keepalive=ka,
                        model_mix=mix, weights=weights)
        return run_sweep(args, images, weights, mix, fpr, ka,
                         tenants=tenants, extra_headers=extra_headers)
    if args.warmup > 0:
        # Same request shape as the timed run: batch parsing + the larger
        # batcher shapes (and every model in the mix) must be warm before
        # the window starts. Tenant-free so warmup doesn't drain quotas.
        closed_loop(args.url, images, 2, args.warmup, args.timeout, Recorder(),
                    files_per_request=fpr, keepalive=ka, model_mix=mix,
                    weights=weights)

    # Server-side stats snapshot BEFORE the timed window: diffing the
    # cumulative stage counters (and the per-replica busy counters)
    # afterwards attributes exactly this run's requests, even on a server
    # that has already seen other traffic.
    stats_before = None
    tracing_before = None
    if not args.no_server_stats:
        stats_before = fetch_stats(args.url, min(args.timeout, 5.0))
        tracing_before = (stats_before or {}).get("tracing")
    hist = None
    if args.history:
        hist = HistoryPoller(args.url, args.duration, args.timeout)
        hist.start()

    rec = Recorder()
    loop_stats = None
    t0 = time.perf_counter()
    if args.rate:
        loop_stats = open_loop(args.url, images, args.rate, args.duration,
                               args.timeout, rec,
                               files_per_request=fpr, keepalive=ka,
                               model_mix=mix, weights=weights,
                               tenants=tenants, extra_headers=extra_headers,
                               size_labels=size_labels)
        mode = f"open({args.rate}/s)"
    else:
        closed_loop(args.url, images, args.workers, args.duration, args.timeout, rec,
                    files_per_request=fpr, keepalive=ka, model_mix=mix,
                    weights=weights, tenants=tenants,
                    extra_headers=extra_headers, size_labels=size_labels)
        mode = f"closed({args.workers})"
    if fpr > 1:
        mode += f"×{fpr}img"
    if size_labels:
        mode += f" sizes({len(sizes)})"
    if tenants:
        mode += f" tenants({len(tenants)})"
    if args.zipf:
        mode += f" zipf({args.zipf:g}×{len(images)})"
    if mix:
        mode += f" mix({len(mix)} models)"
    if not ka:
        mode += " no-keepalive"
    wall = time.perf_counter() - t0

    # Throughput over the offered-load window only: open loop drains
    # in-flight requests after arrivals stop, and counting that tail in the
    # denominator would understate the sustained rate.
    window_end = t0 + args.duration
    in_window = rec.images_completed_by(window_end)
    with rec.lock:  # stragglers may still be appending
        lat = sorted(rec.latencies_ms)
        errors = rec.errors
        connections = rec.connections
        sample_error = rec.sample_error
        per_model = {k: dict(v) for k, v in sorted(rec.per_model.items())}
        sheds_by_reason = dict(rec.sheds_by_reason)
        shed_lat = sorted(rec.shed_latencies_ms)
        per_tenant = {k: {**v, "lat": sorted(v["lat"])}
                      for k, v in sorted(rec.per_tenant.items())}
        per_size = {k: sorted(v) for k, v in sorted(rec.per_size.items())}
        cache_counts = dict(rec.cache_counts)
        image_cache = dict(rec.image_cache)
        lat_hit = sorted(rec.lat_by_cache["hit"])
        lat_miss = sorted(rec.lat_by_cache["miss"])

    def r1(v):
        return None if v is None else round(v, 1)

    summary = {
        "mode": mode,
        "duration_s": round(wall, 2),
        "completed": len(lat),
        "errors": errors,
        # Keep-alive effectiveness, client-side: requests ÷ TCP connections.
        "connections": connections,
        "requests_per_connection": round(len(lat) / connections, 2) if connections else None,
        "images_per_sec": round(in_window / args.duration, 2),
        "latency_ms": {
            "p50": r1(percentile(lat, 50)),
            "p90": r1(percentile(lat, 90)),
            "p99": r1(percentile(lat, 99)),
            "mean": round(sum(lat) / len(lat), 1) if lat else None,
        },
    }
    if loop_stats is not None:
        # Never let an open-loop number be silently client-limited: the
        # summary carries the submit-loop health and the warning is loud.
        summary["submit_loop_utilization"] = loop_stats["submit_loop_utilization"]
        summary["client_limited"] = loop_stats["client_limited"]
        if loop_stats["client_limited"]:
            print(
                "WARNING: load generator saturated "
                f"(submit-loop utilization {loop_stats['submit_loop_utilization']:.0%}, "
                f"{loop_stats['late_arrivals']}/{loop_stats['arrivals']} arrivals late, "
                f"max {loop_stats['max_behind_ms']:.0f} ms behind, "
                f"{loop_stats['thread_cap_drops']} thread-cap drops) — "
                "these numbers measure the CLIENT, not the server; "
                "use more loadgen processes or a lower --rate",
                file=sys.stderr,
            )
    if sum(cache_counts.values()):
        # Response-cache split from the X-Cache headers: hit rate plus the
        # per-hit / per-miss latency columns — a hit answers in HTTP time,
        # a miss (or coalesced wait) pays the device. Absent when the
        # server runs --cache-bytes 0 (no header).
        looked = sum(cache_counts.values())
        summary["cache"] = {
            **cache_counts,
            # Request-level: "hit" means EVERY image of the request hit.
            "hit_rate": round(cache_counts["hit"] / looked, 4),
            # Image-weighted (from the X-Cache "hits=h/n" suffix on batch
            # requests): the number comparable to the server's own
            # /stats → cache hit rate.
            "image_hit_rate": (
                round(image_cache["hit"] / image_cache["total"], 4)
                if image_cache["total"] else None
            ),
            "hit_latency_ms": {
                "p50": r1(percentile(lat_hit, 50)),
                "p99": r1(percentile(lat_hit, 99)),
            },
            "miss_latency_ms": {
                "p50": r1(percentile(lat_miss, 50)),
                "p99": r1(percentile(lat_miss, 99)),
            },
        }
        print(
            f"cache: image hit-rate "
            f"{summary['cache']['image_hit_rate'] or 0:.1%} "
            f"(requests: {cache_counts['hit']} all-hit / "
            f"{cache_counts['miss']} miss / "
            f"{cache_counts['coalesced']} coalesced); "
            f"hit p50 {summary['cache']['hit_latency_ms']['p50']} ms, "
            f"miss p50 {summary['cache']['miss_latency_ms']['p50']} ms",
            file=sys.stderr,
        )
    if per_model:
        # Mixed-model traffic: completions/errors per routed model, so a
        # starved or erroring model in the mix is visible at a glance.
        summary["per_model"] = per_model
    if per_size:
        # Mixed-size traffic (--sizes): the latency split by upload
        # dimensions — small images should not pay large-image wire/decode
        # costs once the server packs them raggedly.
        summary["per_size"] = {
            k: {
                "completed": len(v),
                "p50_ms": r1(percentile(v, 50)),
                "p99_ms": r1(percentile(v, 99)),
            }
            for k, v in per_size.items()
        }
        print("per-size: " + "  ".join(
            f"{k}: {row['completed']} ok"
            + (f" p50 {row['p50_ms']}ms p99 {row['p99_ms']}ms"
               if row["p50_ms"] is not None else "")
            for k, row in summary["per_size"].items()), file=sys.stderr)
    if sheds_by_reason:
        # Shed answers are already inside "errors"; this block splits them
        # out by the server's machine-readable reason and reports how fast
        # the refusals came back — sheds only protect the server if they
        # cost ~HTTP time, not device time.
        summary["sheds"] = {
            "by_reason": sheds_by_reason,
            "answer_ms": {
                "p50": r1(percentile(shed_lat, 50)),
                "p99": r1(percentile(shed_lat, 99)),
            },
        }
    if per_tenant:
        # Per-tenant ledger: who got served, who got shed, and the served
        # tail each tenant saw — the noisy-neighbor isolation numbers.
        tenant_rows = {}
        for name, t in per_tenant.items():
            offered = t["completed"] + t["errors"]
            tenant_rows[name] = {
                "completed": t["completed"],
                "shed": t["shed"],
                "errors": t["errors"],
                "admit_rate": round(t["completed"] / offered, 3)
                if offered else None,
                "shed_rate": round(t["shed"] / offered, 3)
                if offered else None,
                "p50_ms": r1(percentile(t["lat"], 50)),
                "p99_ms": r1(percentile(t["lat"], 99)),
            }
        summary["tenants"] = tenant_rows
        print("per-tenant: " + "  ".join(
            f"{name}: {row['completed']} ok/"
            f"{row['shed']} shed"
            + (f" p99 {row['p99_ms']}ms" if row["p99_ms"] is not None else "")
            for name, row in tenant_rows.items()), file=sys.stderr)
    if sample_error:
        summary["sample_error"] = sample_error
    if rec.sample_trace_id:
        # Join handle against the server's access log / flight recorder.
        summary["sample_trace_id"] = rec.sample_trace_id
    if not args.no_server_stats:
        stats_after = fetch_stats(args.url, min(args.timeout, 5.0))
        # Placement routing's per-chip view: busy fraction + batch count
        # per replica over the window (replicated placements only) —
        # dispersion across chips at a glance. Independent of the tracing
        # block: it reads the staging replicas counters.
        reps = replica_utilization(stats_before, stats_after, args.duration)
        if reps:
            summary["replica_utilization"] = reps
            print("per-replica busy fractions: " + "  ".join(
                f"r{r['replica']}:{r['busy_fraction']:.0%}"
                f"({r['dispatches']} batches)" for r in reps),
                file=sys.stderr)
        attr = stage_attribution(
            tracing_before, (stats_after or {}).get("tracing"))
        if attr:
            summary["server_stages"] = attr
            util = stage_utilization(attr, args.duration)
            if util:
                summary["stage_utilization"] = util
            # Human-readable table on stderr: stdout stays one parseable
            # JSON line for scripts that pipe it.
            print("server-side stage attribution:\n"
                  + format_stage_table(attr, wall_s=args.duration),
                  file=sys.stderr)
            # Closed-loop client-limited flag: if the device executed for
            # only a small fraction of the window while no errors backed
            # requests up, the measured rate was set by the client (or too
            # few workers), not by the server — the closed-loop twin of
            # open loop's submit-loop saturation warning. The span total
            # is divided by the mean batch size first: every request in a
            # batch stamps the full batch's device interval, so the raw
            # sum overcounts device busy-time by exactly that factor.
            dev_util = util.get("device_execute")
            if not args.rate and dev_util is not None and len(lat) > 10:
                dev_busy = dev_util / mean_batch_size(stats_after)
                summary["device_busy_fraction"] = round(dev_busy, 3)
                if dev_busy < 0.5:
                    summary["client_limited"] = True
                    print(
                        f"WARNING: the device was busy only ~{dev_busy:.0%} "
                        "of the window — the server was idle; this "
                        "closed-loop rate is client-limited (add workers or "
                        "loadgen processes)",
                        file=sys.stderr,
                    )
    if hist is not None:
        hist.stop()
        timeline = hist.timeline()
        if timeline:
            summary["server_timeline"] = timeline
            print("server-side timeline (/debug/history):\n"
                  + hist.table(timeline), file=sys.stderr)
        else:
            print("history: /debug/history returned nothing "
                  "(server down or --telemetry-interval 0?)",
                  file=sys.stderr)
    print(json.dumps(summary))
    return 0 if lat else 1


if __name__ == "__main__":
    sys.exit(main())
