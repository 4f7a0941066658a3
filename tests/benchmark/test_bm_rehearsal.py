"""run.py rehearsed on the CPU: it refuses without a TPU, and with the look
for a chip skipped it drives a whole run against a real (tiny) server and
calls altered answers not correct."""

import json
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

from benchmark import run as R
from benchmark.manifest import Cell

ROOT = Path(__file__).resolve().parents[2]
TINY_CONFIG = {
    "model": {"network": "benchmark/reference/nets.py::mobilenet_v2", "input_size": 64, "width": 0.25,
              "num_classes": 16, "dtype": "bfloat16", "topk": 5},
    "server_model": {"name": "mobilenet_v2", "source": "native", "zoo_width": 0.25, "zoo_classes": 16,
                     "input_size": [64, 64], "preprocess": "inception", "dtype": "bfloat16", "topk": 5},
    "http_workers": 4,
    "server_flags": ["--http-workers", "4", "--canvas-buckets", "64,128", "--max-batch", "8"],
    # bfloat16 on the CPU at this size reads about 0.009 / 0.025, answers of
    # other images 0.10 / 0.38 (PERF.md has the chip's readings at full size)
    # (bfloat16 kernels read an int8 share of -0.13 to 0.18 here, the program's int8 tier 0.86 to 1.03)
    "limits": {"logit_rms": 0.04, "logit_max": 0.15, "int8_weight_share": 0.5},
    "check": {"sample_images": 24},
}


def test_in_a_directory_with_only_the_benchmark_it_exits_non_zero_and_prints_no_result(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(ROOT / "benchmark", tmp_path / "benchmark", ignore=shutil.ignore_patterns("__pycache__"))
    cell = json.loads((ROOT / "BENCHMARK.json").read_text())["workloads"][0]["name"]
    proc = subprocess.run([sys.executable, "benchmark/run.py", "--workload", cell,
                           "--seed", "1", "--seconds", "1", "--trace", "0"],
                          cwd=tmp_path, capture_output=True, timeout=120)
    assert proc.returncode != 0
    assert proc.stdout.strip() == b""


def test_a_server_on_another_platform_or_chip_count_is_refused():
    cell = Cell("c", 1, "cfg", TINY_CONFIG, "t", Path("x"), (), ())
    R.check_device({"platform": "tpu", "kind": "TPU v5 lite", "count": 1}, cell, "tpu")
    for device in ({"platform": "cpu", "kind": "cpu", "count": 1},
                   {"platform": "tpu", "kind": "TPU v5 lite", "count": 4}):
        with pytest.raises(R.WrongDevice):
            R.check_device(device, cell, "tpu")


def test_children_do_not_inherit_the_drivers_bench_run(monkeypatch):
    monkeypatch.setenv("BENCH_RUN", "7")
    assert "BENCH_RUN" not in R.child_env() and R.child_env("cpu")["JAX_PLATFORMS"] == "cpu"


def test_a_closed_loops_rate_is_all_the_work_over_all_the_time():
    """Nothing is sent after the window's time is up, everything in flight is
    waited for, and the clock is read after the last answer: a request that
    ends a second past the close counts, and so does that second; one that
    failed counts for nothing but its time."""
    from types import SimpleNamespace as NS
    done = lambda at, ok=True: NS(images=8, done=at, answers=[[]] * 8 if ok else None)
    ctx = NS(setup_s=1.0, seconds=30.0, mix=NS(loop="closed"),
             outcomes=[done(10.0), done(29.5), done(31.0), done(32.0, ok=False)])
    assert R.end_to_end(ctx)["images_per_s"] == pytest.approx(24 / 32.0)
    ctx.outcomes = ctx.outcomes[:2]                      # all answered inside: the window's own length
    assert R.end_to_end(ctx)["images_per_s"] == pytest.approx(16 / 30.0)


def test_a_whole_run_on_the_cpu_and_an_answer_altered_where_it_is_produced(tmp_path, monkeypatch):
    """A real server (tiny MobileNetV2, CPU), booted cold: the run's own line
    says correct, the generator held its pool, the cache never hit; then the
    same outcomes with one image's scores swapped for another's say not
    correct. The harness's look for a chip is skipped, nothing else."""
    monkeypatch.setattr(R, "work_dir", lambda cell: tmp_path)
    e2e = ({"name": "images_per_s", "unit": "images/s"}, {"name": "setup_s", "unit": "s"})
    cell = Cell("tiny-photos", 1, "tiny", TINY_CONFIG, "tiny-photos",
                ROOT / "tests" / "benchmark" / "data" / "tiny-photos.json", e2e, ())
    env = {"JAX_PLATFORMS": "cpu", "JAX_COMPILATION_CACHE_DIR": str(tmp_path / "jax_cache")}
    ctx = R.drive(cell, 2**31 + 99, 3.0, False, require_platform=None,
                  extra_flags=("--aot-cache-dir", str(tmp_path / "aot_cache")), env=env)
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    line = R.report(ctx)
    assert list(line)[-1] == "compared" and list(line)[:5] == ["correct", "attempted", "failed", "metrics", "device"]
    assert line["correct"] is True, line["compared"]
    assert line["failed"] == 0 and line["attempted"] == len(ctx.outcomes) > 20
    assert line["metrics"]["images_per_s"]["value"] > 0 and line["metrics"]["setup_s"]["value"] > 0
    assert line["device"]["platform"] == "cpu"
    assert ctx.result.connections_opened == 4
    cache = lambda s: s["cache"]["hits_total"] + s["cache"]["coalesced_total"]
    assert cache(ctx.after) == cache(ctx.before) == 0, "every image of a run has pixels of its own"
    # the first boot in a checkout compiles, in set-up, and the child that is measured is a second one
    # that loaded what the first compiled; the window compiles nothing
    assert ctx.stats_compile_boot["aot_cache"]["misses_total"] > 0 and ctx.compile_boot_s > 0
    assert ctx.stats_boot["aot_cache"]["misses_total"] == 0
    assert ctx.after["aot_cache"]["misses_total"] == ctx.before["aot_cache"]["misses_total"]
    json.dumps(line)

    # the fault: answers that belong to other images (a slot mix-up in a batch)
    ok = [o for o in ctx.outcomes if o.answers is not None]
    rotated = [o.answers for o in ok[1:]] + [ok[0].answers]
    for o, a in zip(ok, rotated):
        o.answers = a
    tampered = R.report(ctx)
    assert tampered["correct"] is False
    assert tampered["compared"]["logit_rms"]["value"] > 2 * line["compared"]["logit_rms"]["value"]
