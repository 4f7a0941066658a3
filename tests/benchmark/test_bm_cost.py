"""Floors against hand-worked shapes, and the copies against the program's."""

from types import SimpleNamespace

import pytest

from benchmark import cost, loadgen, peaks
from benchmark.reference import conv_floors, nets

MV2 = {"arch": "mobilenet_v2", "network": "benchmark/reference/nets.py::mobilenet_v2", "input_size": 224,
       "num_classes": 1000, "width": 1.0, "dtype": "bfloat16", "topk": 5}
IV3 = {"arch": "inception_v3", "network": "benchmark/reference/nets.py::inception_v3", "input_size": 299,
       "num_classes": 1000, "width": 1.0, "dtype": "bfloat16", "topk": 5}
V5E = (197e12, 819e9)
FLOORS = cost.load_floors({"model": IV3})   # no ``floors.module`` named: the conv classifier's


def _row(canvas, rows_real, batches=1, **more):
    return {"canvas": canvas, "batches": batches, "rows_real": rows_real, **more}


def test_hand_worked_layers():
    ops = nets.ShapeOps()
    x = ops.conv_bn("c", (299, 299, 3, 1 / 3), 32, (3, 3), 2, "VALID")
    assert x == (149, 149, 32, 0.5) and ops.macs["c"] == 149 * 149 * 9 * 3 * 32
    x = ops.dw_bn("d", (112, 112, 32, 0.5), 2, None)
    assert x == (56, 56, 32, 1.0) and ops.macs["d"] == 56 * 56 * 9 * 32
    assert ops.add(x, x)[3] == 2.0 and ops.concat([x, (56, 56, 96, 0.5)]) == (56, 56, 128, 0.625)
    assert ops.head("h", (8, 8, 2048, 0.5), 1000) == (1000,) and ops.macs["h"] == 2048 * 1000
    assert ops.in_moment == {"params/c/conv/kernel": 1 / 3, "params/d/dwconv/kernel": 0.5,
                             "params/h/kernel": 0.5}
    assert ops.params["params/c/conv/kernel"] == (3, 3, 3, 32)
    assert ops.params["params/d/dwconv/kernel"] == (3, 3, 1, 32)


def test_published_sizes():
    assert conv_floors.model_macs("inception_v3", 299, 1000, 1.0) == pytest.approx(5.71e9, rel=0.01)
    assert conv_floors.model_macs("mobilenet_v2", 224, 1000, 1.0) == pytest.approx(0.30e9, rel=0.02)
    assert conv_floors.param_count("inception_v3", 299, 1000, 1.0, with_stats=False) == pytest.approx(23.8e6, rel=0.01)
    assert conv_floors.param_count("mobilenet_v2", 224, 1000, 1.0, with_stats=False) == pytest.approx(3.5e6, rel=0.01)


@pytest.mark.parametrize("model", [MV2, IV3], ids=lambda m: m["arch"])
def test_walkers_equal_the_programs(model):
    from tensorflow_web_deploy_tpu.serving import costmodel
    cfg = SimpleNamespace(name=model["arch"], zoo_width=1.0, zoo_classes=1000,
                          input_size=(model["input_size"],) * 2, dtype="bfloat16")
    theirs = costmodel.model_cost(cfg)
    args = (model["network"], model["input_size"], 1000, 1.0)
    assert conv_floors.model_macs(*args) == theirs["macs_per_image"]
    assert conv_floors.param_count(*args, with_stats=False) == theirs["param_count"]
    for s in (256, 2048):
        assert conv_floors.matmul_resize_flops(s, model["input_size"]) == costmodel.preprocess_flops(
            s, (model["input_size"],) * 2)


def test_peaks_and_percentile_equal_the_programs():
    import importlib.util
    from pathlib import Path
    from tensorflow_web_deploy_tpu.serving import costmodel
    assert peaks.DEVICE_PEAKS == costmodel.DEVICE_PEAKS
    assert peaks.device_peak("TPU v5 lite") == costmodel.device_peak("TPU v5 lite") == V5E
    with pytest.raises(ValueError):
        peaks.device_peak("cpu")
    spec = importlib.util.spec_from_file_location(
        "old_loadgen", Path(__file__).resolve().parents[2] / "tools" / "loadgen.py")
    old = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(old)
    for n in (1, 2, 7, 100, 1001):
        v = [float(i * i) for i in range(n)]
        for q in (50, 90, 95, 99):
            assert loadgen.percentile(v, q) == old.percentile(v, q)


def test_serve_floor_says_which_peak_binds():
    # one MobileNetV2 image on a 2048 canvas: 12.6 MB of canvas at 819 GB/s
    # (15 us) outweighs 0.6 GFLOP at 197 TFLOP/s (3 us)
    t, bound = cost.serve_floor_s(FLOORS, MV2, _row(2048, 1), *V5E)
    assert bound == "bandwidth"
    assert t == pytest.approx((conv_floors.param_count("mobilenet_v2", 224, 1000, 1.0) * 2
                               + 2048 * 2048 * 3 + 40) / 819e9)
    # 32 Inception images on 256 canvases: 366 GFLOP (1.9 ms) against 54 MB (0.07 ms)
    t, bound = cost.serve_floor_s(FLOORS, IV3, _row(256, 32), *V5E)
    assert bound == "compute"
    assert t == pytest.approx(32 * (2 * conv_floors.model_macs("inception_v3", 299, 1000, 1.0)
                                    + 8 * 299 * 299 * 3) / 197e12)
    assert cost.unpack_floor_s(3e6, 5e6, 819e9) == pytest.approx(8e6 / 819e9)


def _ctx(rows, programs, model):
    pad = {f"{r['canvas']}x{r['batch_bucket']}": r for r in rows}
    zero = {k: {**r, "batches": 0, "rows_real": 0, "rows_dispatched": 0, "px_real": 0} for k, r in pad.items()}
    return SimpleNamespace(before={"batcher": {"builders": {"padding": zero}}},
                           after={"batcher": {"builders": {"padding": pad}}},
                           trace={"programs": programs, "busy_s": 1.0, "window_s": 2.0},
                           config={"model": model}, device={"kind": "TPU v5 lite"})


@pytest.mark.parametrize("model", [MV2, IV3], ids=lambda m: m["arch"])
def test_a_program_that_runs_at_its_floor_reads_100_and_never_more(model):
    from benchmark.readers import roofline
    rows = [{"canvas": 512, "batch_bucket": 8, "batches": 10, "rows_real": 60, "rows_dispatched": 80,
             "px_real": 60 * 400 * 300},
            {"canvas": 2048, "batch_bucket": 32, "batches": 5, "rows_real": 160, "rows_dispatched": 160,
             "px_real": 160 * 1900 * 1400}]
    serve_floor = sum(r["batches"] * cost.serve_floor_s(FLOORS, model, r, *V5E)[0]
                      for r in rows)
    unpack_floor = sum(cost.unpack_floor_s(3 * r["px_real"], r["rows_real"] * r["canvas"] ** 2 * 3, V5E[1])
                       for r in rows)
    ctx = _ctx(rows, [["jit_serve", serve_floor, 15], ["jit_unpack_ragged", unpack_floor, 15]], model)
    assert roofline.read(ctx, "serve") == pytest.approx(100.0)
    assert roofline.read(ctx, "unpack") == pytest.approx(100.0)
    assert 0 < roofline.read(ctx, "step_mfu") <= 100.0
    slow = _ctx(rows, [["jit_serve", 4 * serve_floor, 15], ["jit_unpack_ragged", 10 * unpack_floor, 15]], model)
    assert roofline.read(slow, "serve") == pytest.approx(25.0)
    assert roofline.read(slow, "unpack") == pytest.approx(10.0)
    # nothing traced, nothing returned: never a 0 for a share of a roofline
    empty = _ctx(rows, [], model)
    assert all(roofline.read(empty, k) is None for k in ("serve", "unpack", "step_mfu"))


def test_the_share_of_rows_in_the_largest_program_and_the_two_memory_peaks():
    """The metrics that say whether the window's own batches reach the
    program whose working set is the process's peak of device memory."""
    from benchmark.manifest import load_reader
    rows = [{"canvas": 2048, "batch_bucket": 16, "batches": 4, "rows_real": 40, "rows_dispatched": 64, "px_real": 1},
            {"canvas": 4096, "batch_bucket": 16, "batches": 2, "rows_real": 30, "rows_dispatched": 32, "px_real": 1},
            {"canvas": 4096, "batch_bucket": 32, "batches": 5, "rows_real": 130, "rows_dispatched": 160, "px_real": 1}]
    ctx = _ctx(rows, [], IV3)
    share, args = load_reader("top_program_row_share")
    ctx.after["config"] = {"canvas_buckets": [2048, 4096], "batch_buckets": [1, 2, 4, 8, 16, 32]}
    assert share(ctx, **args) == pytest.approx(100.0 * 130 / 200)
    ctx.after["config"]["batch_buckets"].append(64)       # a larger program that no batch reached
    assert share(ctx, **args) == 0.0
    del ctx.after["config"]                                # nothing to read: nothing returned
    assert share(ctx, **args) is None
    ctx.stats_boot = {"device_memory": [{"peak_bytes_in_use": 5_000_000_000}]}
    ctx.after["device_memory"] = [{"peak_bytes_in_use": 6_000_000_000}, {"peak_bytes_in_use": 1}]
    for name, gb in (("peak_hbm_boot_gb", 5.0), ("peak_hbm_gb", 6.0)):
        read, args = load_reader(name)
        assert read(ctx, **args) == pytest.approx(gb)
