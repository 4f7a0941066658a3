"""The readers of a batch's flight: the four phases the program stamps where
each ends (``/stats -> batcher.lifecycle`` ``h2d_s_total``,
``device_queue_s_total``, ``device_s_total``, ``d2h_s_total``) per batch,
and the h2d-bound clock over the window (``readers/window_share.py``). Each
returns None, and does not raise, on a server from before the counters.
``BENCHMARK.json`` does not list them yet: its accepted tests pin the
manifest's ``per_layer`` entries, so the entries come with the ``benchmark``
PR that updates those tests."""

import json
from pathlib import Path
from types import SimpleNamespace

import pytest

from benchmark import manifest as M

ROOT = Path(__file__).resolve().parents[2]
NEW = {"h2d_ms_per_batch": ("ms", "h2d_s_total"), "device_queue_ms_per_batch": ("ms", "device_queue_s_total"),
       "device_ms_per_batch": ("ms", "device_s_total"), "d2h_ms_per_batch": ("ms", "d2h_s_total"),
       "h2d_bound_share": ("%", "h2d_bound_s_total")}


def _ctx(flight=True, seconds=20.0, last_answer=25.0):
    """20 batches in a window of 25 s whose two reads lie 30 s apart: per
    batch 240 ms of copy, 30 of waiting behind earlier calls, 10 on the
    device, 5 of copy back; 12 s of the window only copies flew."""
    def life(batches, scale):
        out = {"batches_total": batches, "inflight_s_total": 0.3 * batches, "starved_s_total": 1.0, "now_s": 0.0}
        if flight:
            out.update(h2d_s_total=0.24 * batches, device_queue_s_total=0.03 * batches,
                       device_s_total=0.01 * batches, d2h_s_total=0.005 * batches, h2d_bound_s_total=scale)
        return out

    before = {"batcher": {"lifecycle": {**life(8, 3.0), "now_s": 1000.0}}}
    after = {"batcher": {"lifecycle": {**life(28, 15.0), "now_s": 1030.0}}}
    outcomes = [SimpleNamespace(answers=[[]], images=1, done=last_answer * (i + 1) / 10) for i in range(10)]
    return SimpleNamespace(before=before, after=after, outcomes=outcomes, seconds=seconds, trace=None)


def _read(name, ctx):
    read, args = M.load_reader(name)
    return read(ctx, **args)


@pytest.mark.parametrize("name", list(NEW))
def test_each_metric_file_loads_a_reader_of_its_counter(name):
    """The files wait for a ``benchmark`` PR to append their entries: each
    loads by name, and its reader's arguments name the counter it reads."""
    man = M.load_manifest()
    reader, args = M.load_reader(name)
    assert callable(reader) and NEW[name][1] in json.dumps(args)
    assert M.NAME.match(name) and (ROOT / "benchmark" / "metrics" / f"{name}.json").is_file()
    # the layer they belong to is the one the accepted device metrics name
    assert {p["layer"] for p in man["per_layer"] if p["name"] == "inflight_ms_per_batch"} == {"device"}


@pytest.mark.parametrize("name, want", [
    ("h2d_ms_per_batch", 240.0),
    ("device_queue_ms_per_batch", 30.0),
    ("device_ms_per_batch", 10.0),
    ("d2h_ms_per_batch", 5.0),
    ("h2d_bound_share", 100.0 * 12.0 / 25.0),     # over the window, not the 30 s between the reads
])
def test_each_reader_on_a_hand_made_window(name, want):
    assert _read(name, _ctx()) == pytest.approx(want)


@pytest.mark.parametrize("name", list(NEW))
def test_an_older_server_reads_none_and_nothing_raises(name):
    """The parent has the lifecycle block but none of the flight's counters."""
    assert _read(name, _ctx(flight=False)) is None


def test_window_share_reads_a_counter_over_the_window_the_throughput_divides_by():
    from benchmark.readers import window_share

    path = "batcher.lifecycle.h2d_bound_s_total"
    # the last answer before --seconds are up: the window is --seconds
    assert window_share.read(_ctx(seconds=30.0, last_answer=20.0), path) == pytest.approx(100.0 * 12.0 / 30.0)
    # any /stats clock: the starved one here
    assert window_share.read(_ctx(), "batcher.lifecycle.starved_s_total") == 0.0
    assert window_share.read(_ctx(), "batcher.lifecycle.no_such_clock") is None
    empty = _ctx()
    empty.outcomes = []
    assert window_share.read(empty, path) is None
    assert (ROOT / "benchmark" / "readers" / "window_share.py").is_file()
