"""The generator against fake servers: schedule, pool size, accounting."""

import json
import socket
import threading
import time
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer

import pytest

from benchmark import loadgen
from benchmark.run import failure_log, judge


class _Req:
    def __init__(self, index):
        self.index, self.images = index, (None,)

    def body(self):
        return b"x" * 10, "image/jpeg"


class _Source:
    def __init__(self):
        self.n = 0
        self.lock = threading.Lock()

    def take(self):
        with self.lock:
            self.n += 1
            return _Req(self.n - 1)


OK_BODY = json.dumps({"predictions": [{"index": i, "score": 0.1} for i in range(5)]}).encode()


def make_server(behaviour):
    """``behaviour(handler, n)`` answers the n-th request (0-based)."""
    state = {"n": 0, "conns": 0, "lock": threading.Lock()}

    class H(BaseHTTPRequestHandler):
        protocol_version = "HTTP/1.1"

        def setup(self):
            super().setup()
            with state["lock"]:
                state["conns"] += 1

        def do_POST(self):
            self.rfile.read(int(self.headers.get("Content-Length", 0)))
            with state["lock"]:
                n = state["n"]
                state["n"] += 1
            behaviour(self, n)

        def log_message(self, *a):
            pass

    srv = ThreadingHTTPServer(("127.0.0.1", 0), H)
    srv.daemon_threads = True
    srv.handle_error = lambda *a: None  # a client that hangs up is part of the test
    threading.Thread(target=srv.serve_forever, daemon=True).start()
    return srv, state


def answer(h, status=200, body=OK_BODY, headers=()):
    h.send_response(status)
    h.send_header("Content-Type", "application/json")
    h.send_header("Content-Length", str(len(body)))
    for k, v in headers:
        h.send_header(k, v)
    h.end_headers()
    h.wfile.write(body)


@pytest.fixture
def server(request):
    srv, state = make_server(request.param)
    yield srv.server_address[1], state
    srv.shutdown()
    srv.server_close()


@pytest.mark.parametrize("server", [lambda h, n: (time.sleep(0.4 if n == 0 else 0.0), answer(h))],
                         indirect=True)
def test_open_loop_latency_runs_from_the_due_time(server):
    """One sender, the first answer stalls 0.4 s: the requests that were due
    meanwhile are sent late, and their latency still counts the wait."""
    port, _ = server
    due = [0.0, 0.1, 0.2, 0.3]
    r = loadgen.run("127.0.0.1", port, "/predict", _Source(), senders=1, seconds=0.5,
                    timeout_s=5, due=due)
    assert [o.index for o in r.outcomes] == [0, 1, 2, 3]
    second = r.outcomes[1]
    assert second.sent >= 0.39 and second.due == 0.1
    assert second.latency_s >= 0.29          # from due, not from send
    assert second.done - second.sent < 0.2   # the server itself was quick
    late = [o for o in r.outcomes if o.sent - o.due > loadgen.LATE_S]
    assert len(late) == 3


@pytest.mark.parametrize("server", [lambda h, n: (time.sleep(0.02), answer(h))], indirect=True)
def test_never_more_connections_than_senders(server):
    port, state = server
    due = [i * 0.002 for i in range(200)]  # 500/s against 4 senders x 50/s: a queue builds
    r = loadgen.run("127.0.0.1", port, "/predict", _Source(), senders=4, seconds=0.4,
                    timeout_s=5, due=due)
    assert len(r.outcomes) == 200 and all(o.status == 200 for o in r.outcomes)
    assert r.connections_opened == 4 == state["conns"]
    assert max(o.conn_requests for o in r.outcomes) >= 40


@pytest.mark.parametrize("server", [lambda h, n: (time.sleep(0.01), answer(h))], indirect=True)
def test_closed_loop_counts_what_was_sent_inside_the_window(server):
    port, state = server
    r = loadgen.run("127.0.0.1", port, "/predict", _Source(), senders=3, seconds=0.3, timeout_s=5)
    assert all(o.sent < 0.3 for o in r.outcomes)
    assert len(r.outcomes) == state["n"] and r.connections_opened == 3
    assert all(o.due is None and o.latency_s == o.done - o.sent for o in r.outcomes)


def _mixed(h, n):
    kind = n % 5
    if kind == 0:
        answer(h)
    elif kind == 1:
        answer(h, 503, json.dumps({"error": "busy", "reason": "backlog"}).encode(), [("Retry-After", "1")])
    elif kind == 2:
        answer(h, 504, json.dumps({"error": "late", "reason": "deadline"}).encode())
    elif kind == 3:
        time.sleep(1.0)  # past the client's time-out
        answer(h)
    else:
        h.connection.shutdown(socket.SHUT_RDWR)  # closes without answering
        h.close_connection = True


@pytest.mark.parametrize("server", [_mixed], indirect=True)
def test_sheds_timeouts_and_closed_connections_are_failed_not_dropped(server):
    port, _ = server
    due = [i * 0.05 for i in range(10)]
    r = loadgen.run("127.0.0.1", port, "/predict", _Source(), senders=1, seconds=0.5,
                    timeout_s=0.3, due=due)
    assert len(r.outcomes) == 10                      # attempted: everything that was due
    for o in r.outcomes:
        judge(o, {"topk": 5})
    ok = [o for o in r.outcomes if o.answers is not None]
    log = failure_log(r.outcomes)
    assert len(ok) + len(log) == 10 and len(ok) >= 1
    assert all(o.status == 200 for o in ok)
    reasons = {row["reason"] for row in log}
    assert {"backlog", "deadline"} <= reasons
    assert any(row["status"] is None and "Timeout" in (row["error"] or "") or "timed out" in (row["error"] or "")
               for row in log)
    assert all({"due_s", "sent_s", "status", "reason", "error", "conn_age_s", "conn_requests", "trace_id"}
               <= set(row) for row in log)


def test_judge_wants_topk_finite_scores_per_image():
    good = loadgen.Outcome(0, 2, status=200, body=json.dumps(
        {"results": [{"predictions": [{"index": i, "score": 0.1} for i in range(5)]}] * 2}).encode())
    assert judge(good, {"topk": 5}) and len(good.answers) == 2
    short = loadgen.Outcome(0, 1, status=200, body=json.dumps(
        {"predictions": [{"index": 1, "score": 0.5}]}).encode())
    nan = loadgen.Outcome(0, 1, status=200, body=OK_BODY.replace(b"0.1", b"NaN", 1))
    shed = loadgen.Outcome(0, 1, status=429, body=b'{"reason": "quota"}')
    assert not any(judge(o, {"topk": 5}) for o in (short, nan, shed))
    assert shed.shed_reason() == "quota" and good.shed_reason() is None


def test_percentile_is_nearest_rank():
    v = [float(i) for i in range(1, 101)]
    assert loadgen.percentile(v, 50) == 51.0 and loadgen.percentile(v, 95) == 95.0
    assert loadgen.percentile([], 50) is None
