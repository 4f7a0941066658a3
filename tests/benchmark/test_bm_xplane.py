"""The reduction from a trace to busy time, programs, operations and idle gaps."""

import json
from pathlib import Path

import pytest

from benchmark import xplane

DATA = Path(__file__).resolve().parent / "data"


def _planes():
    ops = [(0.000, 0.010, "fusion.1"), (0.005, 0.010, "copy.2"),      # overlap: busy 0-15 ms
           (0.030, 0.005, "fusion.1"),                                  # gap 15-30 ms
           (0.100, 0.020, "while.1")]                                   # gap 35-100 ms
    modules = [(0.000, 0.015, "jit_unpack_ragged(7)"), (0.030, 0.005, "jit_serve(12)"),
               (0.100, 0.020, "jit_serve(12)")]
    host = [(0.000, 0.200, "$threading.py:323 wait"), (0.036, 0.060, "np.asarray(jax.Array)"),
            (0.016, 0.013, "PjitFunction(jit_serve)"), (0.040, 0.002, "$engine.py:1484 fetch_outputs")]
    return [{"name": "/device:TPU:0", "lines": [{"name": "XLA Ops", "events": ops},
                                                {"name": "XLA Modules", "events": modules},
                                                {"name": "Steps", "events": [(0.0, 0.12, "0")]}]},
            {"name": "/host:CPU", "lines": [{"name": "python", "events": host}]}]


def test_hand_worked_trace():
    r = xplane.reduce(_planes())
    assert r["busy_s"] == pytest.approx(0.040)
    assert r["window_s"] == pytest.approx(0.200)
    assert r["programs"] == [["jit_serve", pytest.approx(0.025), 2], ["jit_unpack_ragged", pytest.approx(0.015), 1]]
    assert r["device_ops"][0] == ["while.1", pytest.approx(0.020)]
    assert dict(map(tuple, r["device_ops"]))["fusion.1"] == pytest.approx(0.015)
    # the longest gap lies under the D2H wait, the next under the dispatch;
    # Python frames name a gap only where no annotation does
    assert r["idle_gaps"][0] == ["np.asarray(jax.Array)", pytest.approx(0.065)]
    assert r["idle_gaps"][1] == ["PjitFunction(jit_serve)", pytest.approx(0.015)]
    json.dumps(r)


def test_two_chips_average_their_busy_time():
    planes = _planes()
    second = json.loads(json.dumps(planes[0]))
    second["name"] = "/device:TPU:1"
    second["lines"][0]["events"] = [[0.0, 0.010, "fusion.1"]]
    r = xplane.reduce(planes + [second])
    assert r["devices"] == 2 and r["busy_s"] == pytest.approx((0.040 + 0.010) / 2)


def test_a_trace_with_no_device_plane_or_no_op_line_is_an_error_not_a_zero():
    with pytest.raises(ValueError, match="no /device:TPU"):
        xplane.reduce(_planes()[1:])
    planes = _planes()
    planes[0]["lines"] = planes[0]["lines"][1:]
    with pytest.raises(ValueError, match="XLA Ops"):
        xplane.reduce(planes)


def test_union_and_gap_naming():
    assert xplane.union([(3, 4), (0, 1), (0.5, 2)]) == [(0, 2), (3, 4)]
    assert xplane.name_gap((10, 11), [(0, 1, "a")]) == "no host event"
    assert xplane.name_gap((0, 10), [(0, 10, "$py"), (0, 6, "annot")]) == "annot"


def test_a_recorded_chip_trace_reduces_to_its_known_numbers():
    """A slice of a trace recorded on a v5e by this benchmark (see the
    file's own ``about``): the reduction's numbers for it are pinned."""
    path = DATA / "v5e_slice.json"
    doc = json.loads(path.read_text())
    planes = xplane.uncut(doc)
    r = xplane.reduce(planes)
    for key, want in doc["expect"].items():
        assert r[key] == pytest.approx(want, rel=1e-6), key
    assert [[n, pytest.approx(t), c] for n, t, c in doc["expect_programs"]] == r["programs"]
    assert [p[0] for p in r["programs"]] == ["jit__lambda", "jit_serve"]   # the names the metric files match
    assert r["device_ops"][0] == [doc["expect_first_op"][0], pytest.approx(doc["expect_first_op"][1])]
    assert r["device_ops"][0][0] == "while.1 u8[787968]"                    # an HLO line, shortened
    assert r["idle_gaps"][0][0] == doc["expect_first_gap"][0] == "PjitFunction(jit(serve))"
    assert 0 < r["busy_s"] < r["window_s"]
    again = xplane.reduce(xplane.uncut(xplane.cut(planes, 0.0, 1e9)))     # cutting re-bases times only
    assert again["busy_s"] == pytest.approx(r["busy_s"]) and again["programs"][0][2] == 1
