"""The readers of the batch lifecycle: ``/stats -> batcher.lifecycle``,
``compile`` and ``profile``, over hand-made documents and a recorded trace.
Every one returns None, and does not raise, where the server is from before
the block it reads."""

import json
from pathlib import Path
from types import SimpleNamespace

import pytest

from benchmark import manifest as M
from benchmark import xplane

DATA = Path(__file__).resolve().parent / "data"
CELL = "iv3-bigalbums-saturate"
NEW = ("digest_ms_per_img", "lease_wait_ms_per_req", "queue_wait_ms_per_req", "batch_open_ms",
       "launch_wait_ms_per_batch", "sealed_full_share", "inflight_ms_per_batch", "h2d_mb_per_batch",
       "starved_share", "idle_starved_share", "idle_inflight_share", "traced_rows_mean",
       "backend_compiles_in_window")


def _life(batches, full, window, open_s, launch_wait_s, inflight_s, h2d, starved_s, now_s):
    return {"batches_total": batches,
            "by_reason": {"full": full, "arena": 0, "window": window, "flush": 0, "drain": 0},
            "open_s_total": open_s, "launch_wait_s_total": launch_wait_s, "enqueue_s_total": 0.1 * batches,
            "inflight_s_total": inflight_s, "fetch_wait_s_total": 0.5 * inflight_s,
            "h2d_bytes_total": h2d, "d2h_bytes_total": 1280 * batches, "starved_s_total": starved_s,
            "now_s": now_s}


def _stages(**total_ms):
    return {"stages": {k: {"count": 1, "total_ms": v} for k, v in total_ms.items()}}


def _batch(seq, rows, t_launch, t_done):
    return {"seq": seq, "rows": rows, "t_open": t_launch - 0.6, "t_seal": t_launch - 0.1,
            "t_launch": t_launch, "t_done": t_done}


def _ctx(profile="default", trace="default", lifecycle=True, compile_block=True):
    """A window of 10 requests of 8 images and 20 batches, hand-made."""
    before = {"tracing": _stages(cache_lookup=1000.0, lease_wait=50.0, queue_wait=400.0),
              "batcher": {}, "compile": {"backend_compiles_total": 74, "backend_compile_s_total": 190.0}}
    after = {"tracing": _stages(cache_lookup=1000.0 + 80 * 35.0, lease_wait=50.0 + 10 * 120.0,
                                queue_wait=400.0 + 10 * 600.0),
             "batcher": {}, "compile": {"backend_compiles_total": 76, "backend_compile_s_total": 191.5}}
    if lifecycle:
        before["batcher"]["lifecycle"] = _life(8, 2, 6, 4.0, 0.8, 6.0, 8 * 500_000_000, 20.0, 1000.0)
        after["batcher"]["lifecycle"] = _life(28, 17, 11, 4.0 + 20 * 0.45, 0.8 + 20 * 0.02, 6.0 + 20 * 0.7,
                                              28 * 500_000_000, 20.0 + 12.0, 1030.0)
    if not compile_block:
        del before["compile"], after["compile"]
    if profile == "default":
        # a recording of [100, 102.5]: launched 100.2-101.0 and 100.8-101.4 (overlapping), one batch
        # begun before it (99.5-100.1), one still in flight at its end (102.3-), one wholly outside
        profile = {"t_start": 100.0, "t_stop": 102.5, "python_tracer": False,
                   "batches": [_batch(1, 30, 99.5, 100.1), _batch(2, 32, 100.2, 101.0), _batch(3, 10, 100.8, 101.4),
                               _batch(4, 20, 102.3, None), _batch(5, 8, 103.0, 103.5),
                               {"seq": 6, "rows": 4, "t_open": 102.4, "t_seal": None, "t_launch": None, "t_done": None}]}
    if profile is not None:
        after["profile"] = profile
    if trace == "default":
        trace = {"busy_s": 1.0, "window_s": 2.5}
    # the last answer came 25 s into the generator's window; the reads lie 30 s apart
    outcomes = [SimpleNamespace(answers=[[]] * 8, images=8, done=2.5 * (i + 1)) for i in range(10)]
    return SimpleNamespace(before=before, after=after, trace=trace, outcomes=outcomes, seconds=20.0)


def _read(name, ctx):
    read, args = M.load_reader(name)
    return read(ctx, **args)


def test_the_manifest_names_the_thirteen_and_each_finds_its_reader():
    cell = M.load_cell(CELL)
    by_name = {p["name"]: p for p in cell.per_layer}
    assert set(NEW) <= set(by_name)
    for name in NEW:
        spec = by_name[name]
        assert spec["workloads"] == [CELL]
        assert spec["moves"] == ("setup_s" if name == "backend_compiles_in_window" else "images_per_s")
    # the set-up layer's metrics name their cells too: a cell that a later PR adds reports setup_s, not these
    assert all("workloads" in p for p in M.load_manifest()["per_layer"])
    assert by_name["idle_inflight_share"]["source"] == "device_trace"


@pytest.mark.parametrize("name, want", [
    ("digest_ms_per_img", 35.0),
    ("lease_wait_ms_per_req", 120.0),
    ("queue_wait_ms_per_req", 600.0),
    ("batch_open_ms", 450.0),
    ("launch_wait_ms_per_batch", 20.0),
    ("sealed_full_share", 75.0),                 # 15 of the window's 20
    ("inflight_ms_per_batch", 700.0),
    ("h2d_mb_per_batch", 500.0),
    # 12 s starved of the 30 s between the reads, 5 s of them outside the window of 25 s: 7 of 25
    ("starved_share", 28.0),
    # launched: 100.0-100.1, 100.2-101.4, 102.3-102.5 = 1.5 s of 2.5 s
    ("idle_starved_share", 40.0),
    ("idle_inflight_share", 20.0),               # the trace idles 60%
    ("traced_rows_mean", (32 + 10 + 20) / 3),
    ("backend_compiles_in_window", 2.0),
])
def test_each_reader_on_a_hand_made_window(name, want):
    assert _read(name, _ctx()) == pytest.approx(want)


@pytest.mark.parametrize("name", [n for n in NEW if "ms_per_img" not in n and "per_req" not in n])
def test_an_older_server_reads_none_and_nothing_raises(name):
    """The parent has stage totals but no lifecycle, compile or profile block."""
    assert _read(name, _ctx(profile=None, lifecycle=False, compile_block=False)) is None


def test_the_stage_readers_need_no_new_block():
    old = _ctx(profile=None, lifecycle=False, compile_block=False)
    assert _read("digest_ms_per_img", old) == pytest.approx(35.0)
    del old.before["tracing"]["stages"]["lease_wait"], old.after["tracing"]["stages"]["lease_wait"]
    assert _read("lease_wait_ms_per_req", old) is None


def test_the_inflight_share_is_floored_at_zero_and_the_two_shares_add_up():
    ctx = _ctx(trace={"busy_s": 2.0, "window_s": 2.5})       # idle 20%, starved 40%
    assert _read("idle_inflight_share", ctx) == 0.0
    ctx = _ctx()
    idle = 100.0 * (1 - ctx.trace["busy_s"] / ctx.trace["window_s"])
    assert _read("idle_starved_share", ctx) + _read("idle_inflight_share", ctx) == pytest.approx(idle)
    assert _read("idle_inflight_share", _ctx(trace=None)) is None


def test_a_stretch_that_held_no_batch_is_all_starved_and_has_no_rows():
    empty = {"t_start": 5.0, "t_stop": 7.5, "python_tracer": True, "batches": []}
    assert _read("idle_starved_share", _ctx(profile=empty)) == pytest.approx(100.0)
    assert _read("traced_rows_mean", _ctx(profile=empty)) is None
    assert _read("idle_starved_share", _ctx(profile={**empty, "t_stop": 5.0})) is None
    # a window in which nothing was sealed has no per-batch number either
    still = _ctx()
    still.after["batcher"]["lifecycle"] = {**still.before["batcher"]["lifecycle"], "now_s": 1030.0}
    assert _read("batch_open_ms", still) is None and _read("sealed_full_share", still) is None
    # nothing clamps: 5 s of the 30 lay outside the window and none of them starved, which a
    # server that launches nothing outside the window cannot say, so the share shows it below 0
    assert _read("starved_share", still) == pytest.approx(-20.0)
    # a traced run: the profiler wrote its file for 170 s after the window, all of it starved
    late = _ctx()
    late.after["batcher"]["lifecycle"]["now_s"] += 170.0
    late.after["batcher"]["lifecycle"]["starved_s_total"] += 170.0
    assert _read("starved_share", late) == pytest.approx(28.0)
    # the window is the one images_per_s divides by: --seconds where the last answer came sooner
    late.seconds = 28.0
    assert _read("starved_share", late) == pytest.approx(100.0 * (12.0 - 2.0) / 28.0)
    late.outcomes = []
    assert _read("starved_share", late) is None


def test_against_a_recorded_chip_trace():
    """The device's side from a real v5e trace (12 ms, one unpack and one
    serve call): a batch launched over the whole slice leaves every idle
    microsecond in flight, none starved; none launched, the reverse."""
    trace = xplane.reduce(xplane.uncut(json.loads((DATA / "v5e_slice.json").read_text())))
    idle = 100.0 * (1 - trace["busy_s"] / trace["window_s"])
    assert 99.0 < idle < 100.0
    stretch = {"t_start": 50.0, "t_stop": 50.0 + trace["window_s"], "python_tracer": False}
    launched = _ctx(trace=trace, profile={**stretch, "batches": [_batch(9, 3, 49.9, 50.5)]})
    assert _read("idle_starved_share", launched) == pytest.approx(0.0, abs=1e-9)
    assert _read("idle_inflight_share", launched) == pytest.approx(idle)
    assert _read("traced_rows_mean", launched) is None          # launched before the stretch began
    nothing = _ctx(trace=trace, profile={**stretch, "batches": []})
    assert _read("idle_starved_share", nothing) == pytest.approx(100.0)
    assert _read("idle_inflight_share", nothing) == 0.0
