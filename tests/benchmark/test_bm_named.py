"""A configuration names its weights' script, its check child and its floors,
and a network with no convolution in it comes as new files: the toy of
``data/toy_seq`` through ``write_weights``, ``run_check`` and the roofline
reader in a copy of the tree in which no file that was there is edited."""

import json
import os
import shutil
import subprocess
import sys
from pathlib import Path
from types import SimpleNamespace

import numpy as np
import pytest

from benchmark import cost, loadgen, manifest as M, run as R, xplane
from benchmark.reference import leaves

ROOT = Path(__file__).resolve().parents[2]
DATA = Path(__file__).resolve().parent / "data"
TOY = "tests/benchmark/data/toy_seq"
SEED = 2**31 + 351
IV3 = json.loads((ROOT / "benchmark" / "configs" / "iv3-299-bf16-4k.json").read_text())
ENV = {**os.environ, "JAX_PLATFORMS": "cpu"}


class Checkout:
    """A copy of the benchmark with the toy added as a later PR would add it:
    new files under ``paths`` and manifest entries, nothing else."""

    def __init__(self, root: Path):
        self.root = root
        shutil.copytree(ROOT / "benchmark", root / "benchmark", ignore=shutil.ignore_patterns("__pycache__"))
        self.before = {f: f.read_bytes() for f in (root / "benchmark").rglob("*") if f.is_file()}
        shutil.copytree(ROOT / TOY, root / TOY, ignore=shutil.ignore_patterns("__pycache__"))
        shutil.copy(root / TOY / "config.json", root / "benchmark" / "configs" / "toy-seq.json")
        shutil.copy(DATA / "tiny-photos.json", root / "benchmark" / "traffic" / "tiny-photos.json")
        man = json.loads((ROOT / "BENCHMARK.json").read_text())
        man["configs"].append({"name": "toy-seq", "source": "x", "reduced": [], "why": "y",
                               "file": "benchmark/configs/toy-seq.json"})
        man["workloads"].append({"name": "toy-seq-tiny", "config": "toy-seq", "traffic": "tiny-photos",
                                 "chips": 1, "why": "z"})
        for e in man["end_to_end"] + man["per_layer"]:
            if e["name"] in ("images_per_s", "serve_roofline"):
                e["workloads"].append("toy-seq-tiny")
        (root / "BENCHMARK.json").write_text(json.dumps(man))

    def run(self, *argv: str) -> subprocess.CompletedProcess:
        return subprocess.run([sys.executable, *argv], cwd=self.root, env=ENV, capture_output=True, timeout=300)

    def drive(self, fault: str = "") -> dict:
        proc = self.run(f"{TOY}/drive.py", "toy-seq-tiny", str(SEED), "export", *([fault] if fault else []))
        assert proc.returncode == 0, proc.stderr.decode()[-3000:]
        return json.loads(proc.stdout.decode().strip().splitlines()[-1])


@pytest.fixture(scope="module")
def checkout(tmp_path_factory):
    c = Checkout(tmp_path_factory.mktemp("checkout"))
    c.sound = c.drive()
    return c


def test_a_network_with_no_convolution_comes_as_new_files(checkout):
    """Its weights through ``write_weights``, its answers through
    ``run_check``, its floors through the roofline reader, each found by the
    name in its configuration's file, in the copy and not in this tree."""
    out = checkout.sound
    assert out["root"] == str(checkout.root) and out["floors"] == str(checkout.root / TOY / "floors.py")
    assert out["correct"] is True and out["images"] == 6 and out["platform"] == "cpu"
    assert list(out["compared"]) == ["logit_rms", "logit_max"]
    assert out["serve_roofline"] == pytest.approx(100.0)        # a program traced at its floor reads 100
    assert sorted(p.name for p in (checkout.root / "export").iterdir())[:3] == ["attn.k", "attn.o", "attn.q"]
    assert all(f.read_bytes() == data for f, data in checkout.before.items())
    cell = M.load_cell("toy-seq-tiny", M.load_manifest(checkout.root / "BENCHMARK.json"), checkout.root / "benchmark")
    named = M.named(cell.config, checkout.root)
    assert (named.sample_images, named.limit_s, named.answer_steps) == (6, 120.0, 2)
    assert {e["name"] for e in cell.end_to_end} == {"images_per_s", "setup_s"}
    # the set-up layer's metrics name their cells now, so a new cell is not held to them
    assert [p["name"] for p in cell.per_layer] == ["serve_roofline"]


def test_the_toys_floors_go_with_a_rows_tokens():
    """What the old signature could not say: operations with the row's real
    pixels, attention with their square, bytes with the experts touched."""
    config = json.loads((ROOT / TOY / "config.json").read_text())
    floors, m = cost.load_floors(config), config["model"]
    row = lambda px, rows=8, batches=1: {"canvas": 128, "batches": batches, "rows_real": rows, "px_real": rows * px}
    one, two, four = (floors.image_flops(m, row(px)) for px in (64 * 64, 2 * 64 * 64, 4 * 64 * 64))
    assert one < two < four and (four - two) > 2 * (two - one)             # a part that goes with the square
    assert floors.image_flops(m, row(64 * 64, rows=3, batches=2)) == one    # a real image's, whatever the batch
    few = floors.serve_bytes(m, {"canvas": 128, "batches": 1, "rows_real": 1, "px_real": 64})   # one token: two experts
    assert floors.serve_bytes(m, row(64 * 64)) - few > 2 * 2 * 32 * 64 * 2
    t, which = cost.serve_floor_s(floors, m, row(64 * 64), 197e12, 819e9)
    assert which == "bandwidth" and t == pytest.approx(floors.serve_bytes(m, row(64 * 64)) / 819e9)


def test_every_leaf_made_again_alone_equals_the_exports(checkout):
    """A function of (seed, leaf name): any one leaf again, in any order,
    without the tree and without drawing what came before it."""
    net = M.load_module(ROOT / TOY / "net.py", "toy")
    model = json.loads((ROOT / TOY / "config.json").read_text())["model"]
    shapes = net.shapes(model)
    assert len(shapes) == 16
    for name in sorted(shapes, reverse=True):
        written = np.fromfile(checkout.root / "export" / name.replace("/", "."), np.float32)
        again = leaves.normal(SEED, name, shapes[name], net.std(name, shapes[name]))
        assert np.array_equal(written, again.ravel()), name
    assert not np.array_equal(leaves.normal(SEED, "head", (32, 48), 1.0), leaves.normal(SEED + 1, "head", (32, 48), 1.0))
    assert not np.array_equal(leaves.normal(SEED, "head", (32, 48), 1.0), leaves.normal(SEED, "heads", (32, 48), 1.0))


@pytest.mark.parametrize("fault", ["int8:head", "permuted:1"])
def test_an_answer_altered_where_it_is_produced_is_not_correct(checkout, fault):
    """One leaf served from int8; one step's scores moved to other ids: the
    named check child says not correct where it called the sound answers correct."""
    out = checkout.drive(fault)
    assert out["correct"] is False and checkout.sound["correct"] is True
    assert out["compared"]["logit_rms"]["value"] > 5 * out["compared"]["logit_rms"]["limit"]
    assert checkout.sound["compared"]["logit_rms"]["value"] < 0.1 * out["compared"]["logit_rms"]["limit"]


def test_the_control_goes_through_the_named_child(checkout):
    proc = checkout.run("benchmark/control.py", "--workload", "toy-seq-tiny", "--seeds", "5,6",
                        "--controls", "bfloat16_weights")
    assert proc.returncode == 0, proc.stderr.decode()[-3000:]
    lines = [json.loads(ln) for ln in proc.stdout.decode().strip().splitlines()]
    assert [ln["seed"] for ln in lines] == [5, 6]
    for ln in lines:
        assert ln["correct"] is False and ln["control"] == "bfloat16_weights" and ln["images"] == 6
        assert ln["compared"]["logit_rms"]["value"] > 3 * ln["compared"]["logit_rms"]["limit"]


def test_a_configuration_that_names_nothing_gets_todays_files_and_sizes():
    named = M.named(IV3)
    assert {k: IV3.get(k) for k in ("weights", "check", "floors")} == {"weights": None, "check": None, "floors": None}
    assert (named.weights, named.check, named.floors) == (
        ROOT / "benchmark/reference/weights.py", ROOT / "benchmark/check.py", ROOT / "benchmark/reference/conv_floors.py")
    assert (named.sample_images, named.limit_s, named.answer_steps) == (128, 240.0, 1)
    with pytest.raises(FileNotFoundError, match="no_such_check.py"):
        M.named({**IV3, "check": {"child": "benchmark/no_such_check.py"}})


def test_inceptions_floors_through_the_seam_are_the_numbers_they_were():
    """Pinned at commit 40331da from ``cost.image_flops(model)``,
    ``cost.param_count(...)`` and ``cost.serve_floor_s(model, canvas, rows, ...)``."""
    m, floors = IV3["model"], cost.load_floors(IV3)
    assert floors.image_flops(m, {"canvas": 4096, "batches": 5, "rows_real": 94, "px_real": 94 * 12_000_000}) == 11428577816
    assert floors.param_count(m["network"], m["input_size"], m["num_classes"], m["width"]) == 23869000
    for canvas, rows, batches, want in ((4096, 94, 5, (0.0012136431433455435, "bandwidth")),
                                        (4096, 32, 1, (0.0020248498363858365, "bandwidth")),
                                        (2048, 130, 7, (0.0010773858709789703, "compute")),
                                        (2048, 1, 1, (7.365195604395604e-05, "bandwidth"))):
        row = {"canvas": canvas, "batch_bucket": 32, "batches": batches, "rows_real": rows, "px_real": 1}
        assert cost.serve_floor_s(floors, m, row, 197e12, 819e9) == want
    rows = [{"canvas": 4096, "batches": 5, "rows_real": 94}, {"canvas": 2048, "batches": 7, "rows_real": 130}]
    assert cost.step_flops(floors, m, rows) == 11428577816


def _outcome(results: list[dict], images: int | None = None) -> loadgen.Outcome:
    return loadgen.Outcome(0, images or len(results), status=200, body=json.dumps({"results": results}).encode())


PAIRS = [{"index": i, "score": 0.5 / (i + 1)} for i in range(3)]


@pytest.mark.parametrize("steps,result,ok", [
    (1, {"predictions": PAIRS}, True),
    (1, {"steps": [PAIRS]}, True),
    (2, {"steps": [PAIRS, PAIRS[::-1]]}, True),
    (2, {"steps": [PAIRS]}, False),                       # a step short
    (2, {"steps": [PAIRS] * 3}, False),                   # a step over
    (2, {"predictions": PAIRS}, False),                   # one step where two are stated
    (2, {"steps": [PAIRS, PAIRS[:2]]}, False),            # a step with fewer than topk
    (1, {"steps": [[{"index": 0, "score": float("nan")}] * 3]}, False),
], ids=["predictions", "one-step", "two-steps", "short", "over", "predictions-for-two", "thin-step", "nan"])
def test_judge_takes_predictions_or_the_stated_number_of_steps(steps, result, ok):
    model = {"topk": 3, **({"answer_steps": steps} if steps > 1 else {})}
    out = _outcome([result, result])
    assert R.judge(out, model) is ok
    if not ok:
        assert out.answers is None
    elif "steps" in result:                               # as they came: one list of pairs a step
        assert out.answers == [[[(p["index"], p["score"]) for p in step] for step in result["steps"]]] * 2
    else:
        assert out.answers == [[(p["index"], p["score"]) for p in PAIRS]] * 2


def test_the_check_child_is_told_what_was_served_as_it_came_and_has_no_more_than_a_run_has_left(monkeypatch):
    seen = {}

    def fake_run(cmd, input, timeout, **kw):
        seen.update(cmd=cmd, doc=json.loads(input), timeout=timeout)
        return SimpleNamespace(returncode=0, stdout=b'noise\n{"correct": true, "compared": {}}\n', stderr=b"")

    monkeypatch.setattr(R.subprocess, "run", fake_run)
    monkeypatch.setattr(R.traffic, "variant", lambda base, k: b"jpeg-%d" % k)
    steps = [[(3, 0.5), (1, 0.25)], [(7, 0.9), (2, 0.05)]]
    sample = [(SimpleNamespace(index=4, answers=[None, steps]), 1)]
    requests = {4: SimpleNamespace(images=[(None, 0), (None, 9)])}
    config = {**IV3, "check": {"limit_s": 500}}
    cell = M.Cell("c", 1, "cfg", config, "t", Path("x"), (), ())
    assert R.run_check(cell, 11, sample, requests, window_s=30.0) == {"correct": True, "compared": {}}
    assert seen["cmd"][1] == str(ROOT / "benchmark" / "check.py")
    assert seen["doc"]["items"] == [{"jpeg": "anBlZy05", "served": [[[3, 0.5], [1, 0.25]], [[7, 0.9], [2, 0.05]]]}]
    assert seen["doc"]["model"] == IV3["model"] and seen["doc"]["seed"] == 11 and seen["doc"]["control"] is None
    assert seen["timeout"] == R.RUN_LIMIT_S - 30.0 == 330.0            # 500 asked, a run's 360 less its window given
    R.run_check(M.Cell("c", 1, "cfg", IV3, "t", Path("x"), (), ()), 11, sample, requests, window_s=30.0)
    assert seen["timeout"] == 240.0                                      # the default


def _trace_ctx():
    doc = json.loads((DATA / "v5e_slice.json").read_text())
    trace = xplane.reduce(xplane.uncut(doc))
    pad = {"512x1": {"canvas": 512, "batch_bucket": 1, "batches": 2, "rows_real": 2, "rows_dispatched": 2, "px_real": 9}}
    zero = {"512x1": dict(pad["512x1"], batches=0, rows_real=0, rows_dispatched=0, px_real=0)}
    return SimpleNamespace(trace=trace, before={"batcher": {"builders": {"padding": zero}}},
                           after={"batcher": {"builders": {"padding": pad}}})


def test_the_reduction_keeps_what_it_had_and_gains_every_operation():
    was = json.loads((DATA / "v5e_slice.reduced.json").read_text())
    now = _trace_ctx().trace
    assert set(now) == set(was) - {"about"} | {"ops"}
    for key in ("busy_s", "window_s", "devices", "programs", "device_ops", "idle_gaps"):
        assert now[key] == was[key], key
    assert len(now["ops"]) == 595 and [r[:2] for r in now["ops"][:10]] == now["device_ops"]
    assert dict((r[0], r[2]) for r in now["ops"])["dynamic-slice.7 u8[1536]"] == 512
    json.dumps(now)


@pytest.mark.parametrize("args,want", [
    ({"match": "fusion.286 bf16[1,224,224,3]", "program": "jit_serve"}, 4.827e-3),       # the resize: 13th longest
    ({"match": "copy.", "program": "jit_serve"}, 1e3 * 4.8758e-05),                       # many operations, summed
    ({"match": "dynamic-slice.7", "program": "jit__lambda"}, 1e3 * 0.00023457300000000143),
    ({"match": "fusion.286 bf16[1,224,224,3]", "program": "jit_serve", "per": "image"}, 4.827e-3),  # one row a batch
    ({"match": "unpack_planes", "program": "jit_serve"}, None),                            # no such operation
    ({"match": "fusion.286", "program": "jit_decode"}, None),                              # no such program
], ids=["outside-the-ten", "summed", "other-program", "per-image", "no-op", "no-program"])
def test_op_time_reads_an_operation_inside_a_program(args, want):
    from benchmark.readers import op_time
    ctx = _trace_ctx()
    assert "fusion.286" not in " ".join(n for n, _ in ctx.trace["device_ops"])    # the ten longest do not hold it
    got = op_time.read(ctx, **args)
    assert got is None if want is None else got == pytest.approx(want, rel=1e-3)
    with pytest.raises(ValueError):
        op_time.read(ctx, "copy.", "jit_serve", per="batch")
