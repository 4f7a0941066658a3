"""One batch, one span: a ``POST /debug/trace`` recording, the batcher's
batch records and the request spans name the same batches on one clock.

The app runs in-process on the real engine (a tiny MobileNetV2 on the CPU
mesh, ragged wire) behind the pooled HTTP front end. While a few clients
post multi-image requests, the profiler records 300 ms; the ``.xplane.pb``
is then read back with ``ProfileData`` and joined to ``/stats -> profile``
through the ``twd.clock`` markers.
"""

import glob
import http.client
import io
import json
import threading
import time

import numpy as np
import pytest

from tensorflow_web_deploy_tpu.serving.batcher import SEAL_REASONS, Batcher
from tensorflow_web_deploy_tpu.serving.engine import InferenceEngine
from tensorflow_web_deploy_tpu.serving.http import (
    App, make_http_server, shutdown_gracefully,
)
from tensorflow_web_deploy_tpu.utils.config import ModelConfig, ServerConfig

CANVAS, BATCH = 96, 8


def _jpeg(rng, h, w):
    from PIL import Image

    buf = io.BytesIO()
    Image.fromarray((rng.rand(h, w, 3) * 255).astype(np.uint8)).save(buf, "JPEG")
    return buf.getvalue()


def _multipart(jpegs):
    boundary = "twdjoin"
    body = b"".join(
        f"--{boundary}\r\nContent-Disposition: form-data; name=\"f{i}\"; "
        f"filename=\"{i}.jpg\"\r\nContent-Type: image/jpeg\r\n\r\n".encode() + j + b"\r\n"
        for i, j in enumerate(jpegs)) + f"--{boundary}--\r\n".encode()
    return body, f"multipart/form-data; boundary={boundary}"


def _http(port, method, path, body=None, ctype="application/json", timeout=60):
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=timeout)
    try:
        conn.request(method, path, body=body, headers={"Content-Type": ctype})
        r = conn.getresponse()
        return r.status, r.getheader("X-Trace-Id"), r.read()
    finally:
        conn.close()


@pytest.fixture(scope="module")
def server():
    cfg = ServerConfig(
        model=ModelConfig(name="mobilenet_v2", source="native", task="classify",
                          zoo_width=0.25, zoo_classes=12, input_size=(48, 48),
                          preprocess="inception", topk=3),
        canvas_buckets=(CANVAS,), batch_buckets=(BATCH,), max_batch=BATCH,
        ragged=True, wire_format="rgb", max_delay_ms=20.0,
        request_timeout_s=60.0, flight_recorder_recent_n=512, cache_bytes=1 << 20,
    )
    engine = InferenceEngine(cfg)
    engine.warmup()     # every unpack variant: nothing compiles between t_launch and twd.h2d
    batcher = Batcher(engine, max_batch=BATCH, max_delay_ms=20.0, adaptive_delay=False)
    batcher.start()
    app = App(engine, batcher, cfg)
    srv = make_http_server(app, "127.0.0.1", 0, pool_size=8)
    threading.Thread(target=srv.serve_forever, daemon=True).start()
    yield srv.server_address[1], app
    shutdown_gracefully(srv, batcher, grace_s=3.0)
    engine.close()


def _record(port, tmp_path, query="", clients=3, ms=300):
    """Post multi-image requests from ``clients`` threads while one more
    connection records; returns (trace status, the clients' trace IDs)."""
    stop, ids, lock = threading.Event(), [], threading.Lock()

    def client(k):
        rng = np.random.RandomState(1000 + k)
        while not stop.is_set():
            jpegs = [_jpeg(rng, rng.randint(20, CANVAS), rng.randint(20, CANVAS)) for _ in range(3)]
            status, tid, _ = _http(port, "POST", "/predict", *_multipart(jpegs))
            assert status == 200
            with lock:
                ids.append(tid)

    threads = [threading.Thread(target=client, args=(k,)) for k in range(clients)]
    for t in threads:
        t.start()
    try:
        time.sleep(0.1)
        status, _, body = _http(port, "POST", f"/debug/trace?ms={ms}&dir={tmp_path}{query}")
    finally:
        stop.set()
        for t in threads:
            t.join(timeout=60)
    return status, body, ids


def _events(trace_dir):
    """{name: [(start_ns, duration_ns, stats)]} of the host planes' events
    whose names matter here, and the count of ``$file:line`` Python frames."""
    from jax.profiler import ProfileData

    files = sorted(glob.glob(f"{trace_dir}/**/*.xplane.pb", recursive=True))
    assert files, f"no .xplane.pb under {trace_dir}"
    out, frames = {}, 0
    for plane in ProfileData.from_file(files[-1]).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith("$"):
                    frames += 1
                elif e.name.startswith("twd."):
                    out.setdefault(e.name.split(" ")[0], []).append(
                        (e.start_ns, e.duration_ns, dict(e.stats)))
    return out, frames


def test_a_recording_joins_batches_spans_and_annotations_on_one_clock(server, tmp_path):
    port, app = server
    status, body, ids = _record(port, tmp_path)
    assert status == 200, body
    _, _, raw = _http(port, "GET", "/stats")
    profile = json.loads(raw)["profile"]
    assert profile["python_tracer"] is False and profile["trace_dir"] == str(tmp_path)
    assert 0.29 < profile["t_stop"] - profile["t_start"] < 1.0

    events, frames = _events(tmp_path)
    assert frames == 0          # the Python tracer is off by default
    clocks = sorted(events["twd.clock"])
    assert len(clocks) == 2 and all("mono_ns" in st for _, _, st in clocks)
    # the marker's start on the profiler's clock beside its own monotonic
    # reading is the offset; both markers give the same one
    offsets = [st["mono_ns"] - start for start, _, st in clocks]
    assert abs(offsets[0] - offsets[1]) < 1e6
    assert clocks[0][2]["mono_ns"] / 1e9 == pytest.approx(profile["t_start"], abs=1e-6)
    assert clocks[1][2]["mono_ns"] / 1e9 == pytest.approx(profile["t_stop"], abs=1e-6)
    offset_s = offsets[0] / 1e9

    inside = [r for r in profile["batches"]
              if r["t_launch"] and profile["t_start"] <= r["t_launch"]
              and r["t_done"] and r["t_done"] <= profile["t_stop"]]
    assert len(inside) >= 3, profile["batches"]
    by_seq = {name: {st["seq"]: (start / 1e9 + offset_s, dur / 1e9) for start, dur, st in events[name]}
              for name in ("twd.h2d", "twd.h2d_flight", "twd.unpack_enqueue", "twd.serve_enqueue",
                           "twd.d2h_start", "twd.fetch")}
    flight_stats = {st["seq"]: st for _, _, st in events["twd.h2d_flight"]}
    late = []
    for rec in inside:
        seq = rec["seq"]
        h2d_start, h2d_dur = by_seq["twd.h2d"][seq]
        fetch_start, fetch_dur = by_seq["twd.fetch"][seq]
        # the copy's own annotation: opened as the device_put starts, closed
        # where the copy's end is stamped, by the thread that waited for it
        flight_start, flight_dur = by_seq["twd.h2d_flight"][seq]
        assert flight_start <= h2d_start + 1e-4
        assert flight_stats[seq]["h2d_bytes"] == rec["h2d_bytes"] and flight_stats[seq]["rows"] == rec["rows"]
        # Through the offset an annotation and the record's stamp beside it
        # agree within 5 ms. Between the two clock reads lie a few lines of
        # Python: on a loaded machine the interpreter may hand the thread's
        # turn away there (its switch interval is 5 ms), so one batch of a
        # recording may be late; a wrong offset would move them all.
        for apart in (h2d_start - rec["t_launch"], h2d_start + h2d_dur - rec["t_put"],
                      fetch_start - rec["t_fetch"], flight_start + flight_dur - rec["t_h2d_done"]):
            assert -1e-4 < apart, (rec, apart)
            if apart >= 5e-3:
                late.append((seq, apart))
        assert fetch_start + fetch_dur <= rec["t_done"] + 1e-3
        assert rec["t_open"] <= rec["t_seal"] <= rec["t_launch"] <= rec["t_put"] <= rec["t_pre"] \
            <= rec["t_launched"] <= rec["t_done"] and rec["t_launched"] <= rec["t_fetch"] <= rec["t_done"]
        assert rec["t_launch"] <= rec["t_h2d_done"] <= rec["t_dev_start"] <= rec["t_ready"] <= rec["t_done"]
        assert rec["reason"] in SEAL_REASONS
        assert rec["h2d_bytes"] > 0 and rec["d2h_bytes"] > 0
        assert seq in by_seq["twd.unpack_enqueue"] and seq in by_seq["twd.serve_enqueue"] \
            and seq in by_seq["twd.d2h_start"]
    assert len({seq for seq, _ in late}) <= 1, late
    # the request-side stages are there too, under their own names
    for name in ("twd.http_read", "twd.body_read", "twd.lease_wait", "twd.image_decode",
                 "twd.cache_lookup", "twd.staging_write", "twd.await_batch", "twd.postprocess",
                 "twd.serialize", "twd.seal_wait"):
        assert events.get(name), name
    assert {st["trace_id"] for _, _, st in events["twd.await_batch"]} & set(ids)

    # a request span leads to its batches and back
    spans = {d["trace_id"]: d for _, _, d in app.obs.flight.trace_records(None)}
    records = {r["seq"]: r for r in app.batcher.batch_timeline()}
    checked = 0
    for tid in ids:
        rode = spans[tid]["meta"]["batches"]
        assert 1 <= len(rode) <= 3 and len(set(rode)) == len(rode)
        for seq in rode:
            if seq in records:
                assert tid in records[seq]["trace_ids"]
                checked += 1
    for rec in inside:
        for tid in rec["trace_ids"]:
            assert rec["seq"] in spans[tid]["meta"]["batches"]
    assert checked >= len(inside)

    # GET /debug/trace carries the same join in its events' args
    _, _, raw = _http(port, "GET", "/debug/trace?last_s=30")
    doc = json.loads(raw)
    fetch_legs = [e for e in doc["traceEvents"] if e.get("cat") == "batch" and " fetch" in e["tid"]]
    assert fetch_legs and all({"reason", "h2d_bytes", "trace_ids", "t_put", "t_h2d_done", "t_ready"}
                              <= set(e["args"]) for e in fetch_legs)
    # the copy and the device phase are drawn as intervals of each batch
    for leg, a, z in (("copy", "t_launch", "t_h2d_done"), ("device", "t_dev_start", "t_ready")):
        drawn = [e for e in doc["traceEvents"] if e.get("cat") == "batch" and e["tid"].endswith(f" {leg}")]
        assert drawn and all(e["ts"] == pytest.approx(e["args"][a] * 1e6, abs=0.1) if a in e["args"] else True
                             for e in drawn)
        assert all(e["dur"] == pytest.approx(max(0.1, (e["args"][z] - e["args"].get(a, e["ts"] / 1e6)) * 1e6),
                                              abs=0.3) for e in drawn)
    begun = [e for e in doc["traceEvents"] if e.get("ph") == "b" and e["args"].get("trace_id") in ids]
    assert begun and all(e["args"]["batches"] for e in begun)


def test_a_second_recording_meanwhile_is_refused_and_python_frames_are_opt_in(server, tmp_path):
    port, _ = server
    answers = {}

    def first():
        answers["first"] = _http(port, "POST", f"/debug/trace?ms=400&dir={tmp_path}/a&python=1")[0]

    t = threading.Thread(target=first)
    t.start()
    time.sleep(0.15)
    status, _, body = _http(port, "POST", f"/debug/trace?ms=50&dir={tmp_path}/b")
    t.join(timeout=60)
    assert answers["first"] == 200
    assert status == 409 and b"already" in body
    assert not glob.glob(f"{tmp_path}/b/**/*.xplane.pb", recursive=True)
    events, frames = _events(f"{tmp_path}/a")
    assert frames > 0 and len(events["twd.clock"]) == 2
    _, _, raw = _http(port, "GET", "/stats")
    assert json.loads(raw)["profile"]["python_tracer"] is True
    # and the flag is free again
    assert _http(port, "POST", f"/debug/trace?ms=20&dir={tmp_path}/c")[0] == 200


def test_stats_carry_lifecycle_and_compile_blocks(server):
    port, _ = server
    rng = np.random.RandomState(5)
    _, _, raw0 = _http(port, "GET", "/stats")
    assert _http(port, "POST", "/predict", *_multipart([_jpeg(rng, 40, 50), _jpeg(rng, 30, 30)]))[0] == 200
    _, _, raw1 = _http(port, "GET", "/stats")
    a, b = json.loads(raw0), json.loads(raw1)
    la, lb = a["batcher"]["lifecycle"], b["batcher"]["lifecycle"]
    assert lb["batches_total"] > la["batches_total"]
    assert sum(lb["by_reason"].values()) == lb["batches_total"]
    assert lb["h2d_bytes_total"] > la["h2d_bytes_total"] and lb["d2h_bytes_total"] > la["d2h_bytes_total"]
    assert lb["unpack_kernel_batches_total"] == 0  # the CPU takes the XLA gather
    assert lb["now_s"] > la["now_s"] and lb["starved_s_total"] >= la["starved_s_total"]
    # the engine compiled at boot and nothing since: warm-up covered every shape
    assert a["compile"]["backend_compiles_total"] > 0 and a["compile"]["backend_compile_s_total"] > 0
    assert b["compile"] == a["compile"]


def test_scopes_name_the_phases_and_the_modules_keep_their_names(server):
    """``jit_serve`` and ``jit__lambda`` are what three metric files match
    the traced programs by; the named scopes put the phase into each op's
    metadata and leave the module's name alone."""
    import re

    _, app = server
    engine = app.engine
    rep = engine._replicas[0]
    exe, _ = engine._get_serve_exe(rep, CANVAS, BATCH)
    text = exe.as_text()
    assert re.match(r"HloModule jit_serve\b", text)
    for scope in ("resize", "forward", "topk"):
        assert re.search(rf'op_name="jit\(serve\)/{scope}/', text), scope
    unpack, _, kernel = engine._ragged_unpack(rep, CANVAS, BATCH, 2)
    assert not kernel  # the CPU takes the XLA gather
    text = unpack.as_text()
    assert re.match(r"HloModule jit__lambda\b", text)
    assert 'op_name="jit(<lambda>)/unpack/' in text


def test_trace_batches_reads_a_recording_and_names_what_lies_over_a_gap(server, tmp_path, capsys):
    """tools/trace_batches.py on a CPU recording (clock, annotations and the
    batches' stamped phases; the CPU has no device plane, so no gaps and no
    programs), then its gap and join tables on hand-made device lines."""
    from tools import trace_batches as T

    port, _ = server
    status, _, _ = _record(port, tmp_path, clients=2, ms=150)
    assert status == 200
    _, _, raw = _http(port, "GET", "/stats")
    (tmp_path / "stats.json").write_text(raw.decode())
    assert T.main([str(tmp_path), "--stats", str(tmp_path / "stats.json")]) == 0
    doc = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert doc["clock"]["markers"] == 2 and doc["clock"]["offset_drift_s"] < 1e-3
    assert 0.14 < doc["clock"]["recorded_s"] < 1.0
    assert doc["twd_events"] > 20 and doc["idle_gaps"] == [] and doc["unjoined"] == 0
    profile = json.loads(raw)["profile"]
    stamped = {b["seq"] for b in profile["batches"] if b["t_ready"] is not None}
    assert stamped and {r["seq"] for r in doc["batches"]} == stamped
    for r in doc["batches"]:
        assert r["programs"] == [] and min(r["h2d_ms"], r["device_queue_ms"], r["device_ms"], r["d2h_ms"]) >= 0

    # device busy 0-10 ms and 60-70 ms: one gap of 50 ms, under batch 7's fetch and a seal wait
    twd = sorted([(0.001, 0.004, "twd.h2d c4096 b32", {"seq": 7, "rows": 30}),
                  (0.004, 0.005, "twd.unpack_enqueue c4096 b32", {"seq": 7, "rows": 30}),
                  (0.008, 0.065, "twd.fetch c4096 b32", {"seq": 7, "rows": 30}),
                  (0.020, 0.040, "twd.seal_wait c4096", {}),
                  (0.0, 0.0001, "twd.clock", {"mono_ns": 5_000_000_000})])
    ops = [(0.0, 0.010), (0.060, 0.070), (0.005, 0.008)]
    batches = [{"seq": 7, "t_launch": 5.001, "t_done": 5.065, "h2d_bytes": 1_000_000_000},
               {"seq": 8, "t_launch": 5.2, "t_done": None}]
    (gap,) = T.idle_gaps(twd, ops, 10, T.clock(twd)["offset_s"], batches)
    assert gap["ms"] == pytest.approx(50.0) and gap["launched"] == [7]
    assert [(r["name"], r["seq"]) for r in gap["batch_spans"]] == [("twd.fetch c4096 b32", 7)]
    assert [r["name"] for r in gap["under"]] == ["twd.fetch c4096 b32", "twd.seal_wait c4096"]
    assert gap["under"][0]["share"] == 1.0 and gap["under"][1]["share"] == pytest.approx(0.4)
    # each program joins the batch whose outputs' stamp is the first at or
    # after its end: batch 6 ends with the recording's first serve, batch 7
    # with the second; a program after the last stamp joins none
    modules = [(0.0005, 0.0009, "jit_serve(5)"),
               (0.0300, 0.0600, "jit__lambda(3)"), (0.0600, 0.0700, "jit_serve(5)"),
               (0.0800, 0.0850, "jit__lambda(3)")]
    stamps = {"t_launch": 4.95, "t_h2d_done": 4.96, "t_dev_start": 4.98, "t_ready": 5.0012, "t_done": 5.002,
              "late": ()}
    batches = [{"seq": 6, **stamps},
               {**batches[0], "t_h2d_done": 5.004, "t_dev_start": 5.030, "t_ready": 5.0701, "t_done": 5.072,
                "late": ("t_ready",)},
               {"seq": 8, "t_launch": 5.2, "t_ready": None}]
    rows, unjoined = T.join(modules, T.clock(twd)["offset_s"], batches)
    assert unjoined == 1 and [r["seq"] for r in rows] == [6, 7]
    six, seven = rows
    assert [p["name"] for p in six["programs"]] == ["jit_serve(5)"] and six["stamp_lag_ms"] == pytest.approx(0.3)
    assert [(p["name"], p["ms"]) for p in seven["programs"]] == [("jit__lambda(3)", 30.0), ("jit_serve(5)", 10.0)]
    assert seven["stamp_lag_ms"] == pytest.approx(0.1) and seven["late"] == ["t_ready"]
    assert (seven["h2d_ms"], seven["device_queue_ms"], seven["device_ms"], seven["d2h_ms"]) == \
        pytest.approx((3.0, 26.0, 40.1, 1.9))


def test_metrics_carry_the_lifecycle_counters(server):
    """``GET /metrics`` exports ``/stats -> batcher.lifecycle`` with the
    model's labels: batches by reason, the phase clocks (the flight's four
    among them), bytes each way and the other counts, late stamps too."""
    import re

    port, _ = server
    rng = np.random.RandomState(9)
    assert _http(port, "POST", "/predict", *_multipart([_jpeg(rng, 40, 50)]))[0] == 200
    _, _, raw = _http(port, "GET", "/stats")
    life = json.loads(raw)["batcher"]["lifecycle"]
    _, _, text = _http(port, "GET", "/metrics")
    text = text.decode()
    samples = {}   # (family, the label that is not the model's) -> value
    for line in text.splitlines():
        m = re.match(r"tpu_serve_lifecycle_(\w+)\{(.*)\} (\S+)$", line)
        if m:
            labels = dict(re.findall(r'(\w+)="([^"]*)"', m[2]))
            assert (labels.pop("model"), labels.pop("version")) == ("mobilenet_v2", "1")
            ((_, what),) = labels.items()
            samples[m[1], what] = float(m[3])
    assert "# TYPE tpu_serve_lifecycle_seconds_total counter" in text
    for phase in ("open", "launch_wait", "inflight", "h2d", "device_queue", "device", "d2h", "h2d_bound",
                  "starved"):
        assert ("seconds_total", phase) in samples, phase
    assert samples["seconds_total", "h2d"] >= life["h2d_s_total"] > 0
    assert samples["bytes_total", "h2d"] >= life["h2d_bytes_total"] > 0
    reasons = {k: v for k, v in samples.items() if k[0] == "batches_total"}
    assert len(reasons) == len(SEAL_REASONS) and sum(reasons.values()) >= life["batches_total"] > 0
    for counter in ("stamps_late", "window_holds", "unpack_kernel_batches"):
        assert ("counts_total", counter) in samples, counter
    # what a decoder's program counts a call rides the same family
    from tensorflow_web_deploy_tpu.serving.http import _lifecycle_metrics
    from tensorflow_web_deploy_tpu.utils.metrics import PromText

    p = PromText()
    _lifecycle_metrics(p, {"tokens_real_total": 7.0, "answer_steps_cached_total": 15.0, "now_s": 1.0},
                       {"model": "nemotron_h", "version": 1})
    assert p.render().splitlines()[2:] == [
        'tpu_serve_lifecycle_counts_total{counter="tokens_real",model="nemotron_h",version="1"} 7',
        'tpu_serve_lifecycle_counts_total{counter="answer_steps_cached",model="nemotron_h",version="1"} 15']
