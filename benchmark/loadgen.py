"""The load generator: a fixed pool of senders, each on one connection.

The server's front end is a fixed pool of worker threads that own
connections for their whole lifetime (``--http-workers``), so a client that
opens more connections than that leaves some that no worker serves. This
generator therefore holds exactly ``senders`` persistent connections, as a
reverse proxy with a bounded upstream pool would, and never more.

- Closed loop: every sender sends its next request as soon as the last one
  is answered, until the window ends. A request counts if it was *sent*
  inside the window; the ones in flight at the end are waited for.
- Open loop: due times are fixed before the window (``traffic.schedule``).
  A free sender claims the next arrival in order and sleeps until it is due;
  when every sender is busy the arrival waits, is sent late, and its latency
  still runs from its due time. A request counts if it was *due* inside the
  window.

Nothing is retried on a time-out or an HTTP status; a connection-level fault
(the server closed an idle kept-alive socket) is retried once on a fresh
socket, as ``tools/loadgen.py::HttpClient`` does, whose logic this copies.
"""

from __future__ import annotations

import http.client
import itertools
import json
import threading
import time
from dataclasses import dataclass, field

LATE_S = 0.005  # an arrival sent more than this after it was due was late
TRACE_TIMEOUT_S = 240.0


def percentile(sorted_values: list[float], q: float) -> float | None:
    """q-th percentile of an ascending list, nearest rank (a copy of
    ``tools/loadgen.py::percentile``); None when empty."""
    if not sorted_values:
        return None
    i = min(len(sorted_values) - 1, int(round(q / 100 * (len(sorted_values) - 1))))
    return sorted_values[i]


@dataclass
class Outcome:
    """One request as the generator saw it. Times are seconds from the
    window's start on the monotonic clock."""

    index: int
    images: int
    due: float | None = None      # open loop only
    sent: float = 0.0
    done: float = 0.0
    status: int | None = None     # None: no HTTP answer (time-out, reset)
    body: bytes = b""
    trace_id: str | None = None
    x_cache: str | None = None
    error: str | None = None      # exception class and text
    conn_age_s: float = 0.0       # the connection's age when the request left
    conn_requests: int = 0        # requests it had carried before this one
    answers: list | None = None   # per-image predictions, set by judge()

    @property
    def latency_s(self) -> float:
        return self.done - (self.sent if self.due is None else self.due)

    def shed_reason(self) -> str | None:
        if self.status in (429, 503, 504):
            try:
                return json.loads(self.body).get("reason") or f"http_{self.status}"
            except (ValueError, AttributeError):
                return f"http_{self.status}"
        return None


class Connection:
    """One persistent HTTP/1.1 connection with a single reconnect on a
    connection-level fault."""

    def __init__(self, host: str, port: int, timeout_s: float, opened: list):
        self.host, self.port, self.timeout_s = host, port, timeout_s
        self.conn: http.client.HTTPConnection | None = None
        self.born = 0.0
        self.carried = 0
        self._opened = opened  # shared tally: one entry per TCP connect

    def _connect(self):
        conn = http.client.HTTPConnection(self.host, self.port, timeout=self.timeout_s)
        try:
            conn.connect()
        except Exception:
            conn.close()
            raise
        self.conn, self.born, self.carried = conn, time.monotonic(), 0
        self._opened.append(self.born)

    def close(self):
        if self.conn is not None:
            try:
                self.conn.close()
            finally:
                self.conn = None

    def post(self, path: str, body: bytes, ctype: str, out: Outcome,
             timeout_s: float | None = None) -> None:
        """``timeout_s`` overrides the connection's for this one exchange."""
        for attempt in (0, 1):
            if self.conn is None:
                self._connect()
            self.conn.sock.settimeout(timeout_s or self.timeout_s)
            out.conn_age_s = time.monotonic() - self.born
            out.conn_requests = self.carried
            try:
                self.conn.request("POST", path, body=body, headers={"Content-Type": ctype})
                resp = self.conn.getresponse()
                out.body = resp.read()
                out.status = resp.status
                out.trace_id = resp.getheader("X-Trace-Id")
                out.x_cache = resp.getheader("X-Cache")
            except TimeoutError:
                # The request reached the server and the answer timed out: a
                # retry would send the image twice.
                self.close()
                raise
            except (http.client.HTTPException, OSError):
                self.close()
                if attempt:
                    raise
                continue
            self.carried += 1
            if resp.will_close:
                self.close()
            return


def latencies_ms(outcomes: list[Outcome], timeout_s: float) -> list[float]:
    """Every request's latency, ascending. One with no correct answer waited
    at least its client time-out, and counts as that."""
    return sorted((o.latency_s if o.answers is not None else max(o.latency_s, timeout_s)) * 1e3
                  for o in outcomes)


@dataclass
class Result:
    outcomes: list[Outcome] = field(default_factory=list)
    connections_opened: int = 0
    window_s: float = 0.0
    trace_status: int | str | None = None


def run(host: str, port: int, path: str, source, *, senders: int, seconds: float,
        timeout_s: float, due: list[float] | None = None,
        trace: tuple[float, str] | None = None) -> Result:
    """Drive ``POST path`` for ``seconds``. ``source.take()`` yields the
    requests in order; ``due`` (open loop) gives each one's due time from the
    window's start, and its length is the number of requests attempted.

    ``trace`` = (seconds into the window, path): the first sender POSTs that
    path once, on its own connection, in place of a request. The server's
    profiler route holds an HTTP worker for as long as it records, so an
    extra connection for it would leave one sender's unserved."""
    opened: list[float] = []
    lock = threading.Lock()
    outcomes: list[Outcome] = []
    counter = itertools.count()
    # Requests are dealt before the window where their number is known.
    prepared = [source.take() for _ in due] if due is not None else None
    t0 = time.monotonic() + 0.05  # every sender is parked on the clock by then

    def sender(first: bool):
        conn = Connection(host, port, timeout_s, opened)
        traced = trace is None or not first
        try:
            while True:
                if not traced and time.monotonic() - t0 >= trace[0]:
                    traced = True
                    out = Outcome(-1, 0)
                    try:  # the profiler writes its file before it answers
                        conn.post(trace[1], b"", "application/json", out, timeout_s=TRACE_TIMEOUT_S)
                        result.trace_status = out.status
                    except (OSError, http.client.HTTPException) as e:
                        result.trace_status = f"{type(e).__name__}: {e}"
                with lock:
                    i = next(counter)
                if due is not None:
                    if i >= len(due):
                        return
                    req = prepared[i]
                    wait = t0 + due[i] - time.monotonic()
                    if wait > 0:
                        time.sleep(wait)
                else:
                    wait = t0 - time.monotonic()
                    if wait > 0:
                        time.sleep(wait)
                    if time.monotonic() - t0 >= seconds:
                        return
                    req = source.take()
                body, ctype = req.body()
                out = Outcome(req.index, len(req.images), due=None if due is None else due[i])
                out.sent = time.monotonic() - t0
                try:
                    conn.post(path, body, ctype, out)
                except Exception as e:  # the boundary: every fault becomes an outcome
                    out.error = f"{type(e).__name__}: {e}"
                out.done = time.monotonic() - t0
                with lock:
                    outcomes.append(out)
        finally:
            conn.close()

    result = Result(window_s=seconds)
    threads = [threading.Thread(target=sender, args=(i == 0,), name=f"sender-{i}", daemon=True)
               for i in range(senders)]
    for t in threads:
        t.start()
    # Every outstanding answer is waited for: the window, then one client
    # time-out for the last request sent, then a margin.
    deadline = t0 + seconds + timeout_s + 5.0 + (TRACE_TIMEOUT_S if trace else 0.0)
    for t in threads:
        t.join(timeout=max(0.0, deadline - time.monotonic()))
    alive = [t.name for t in threads if t.is_alive()]
    if alive:
        raise RuntimeError(f"senders still running {timeout_s + 5.0:.0f} s past the window: {alive}")
    outcomes.sort(key=lambda o: o.index)
    result.outcomes, result.connections_opened = outcomes, len(opened)
    return result
