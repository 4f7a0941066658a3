"""BENCHMARK.json keeps to the contract, and every name finds its files."""

import json
import re
import shutil
from pathlib import Path

import pytest

from benchmark import manifest as M

ROOT = Path(__file__).resolve().parents[2]
MAN = json.loads((ROOT / "BENCHMARK.json").read_text())
METRICS = MAN["end_to_end"] + MAN["per_layer"]
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


def test_top_level_keys_and_command():
    assert set(MAN) == {"command", "paths", "run_seconds", "configs", "workloads", "end_to_end", "per_layer"}
    assert MAN["paths"] == ["benchmark", "tests/benchmark"]
    assert MAN["command"] == ["python3", "benchmark/run.py"]
    assert isinstance(MAN["run_seconds"], int) and 1 <= MAN["run_seconds"] <= 51
    # a full check with 24 cells has to fit: 2 + 14 x cells runs
    runs = 2 + 14 * 24
    assert runs * (MAN["run_seconds"] + 60) + 24 * 2 * 90 + 1200 <= 43200
    assert len((ROOT / "BENCHMARK.json").read_bytes()) <= 64 * 1024


@pytest.mark.parametrize("entry", MAN["configs"] + MAN["workloads"] + METRICS, ids=lambda e: e["name"])
def test_names_units_and_lines(entry):
    assert M.NAME.match(entry["name"])
    for key in ("config", "traffic"):
        if key in entry:
            assert M.NAME.match(entry[key])
    if "unit" in entry:
        assert M.UNIT.match(entry["unit"])
        assert entry["better"] in ("lower", "higher")
        assert entry["source"] in SOURCES
    for key in ("why", "layer", "source"):
        if key in entry:
            assert 1 <= len(entry[key]) <= 200 and "\n" not in entry[key] and "\t" not in entry[key]


def test_entries_have_exactly_the_contract_keys():
    for c in MAN["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert c["reduced"] == []
    for w in MAN["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert w["chips"] in (1, 4)
    for e in MAN["end_to_end"]:
        assert set(e) - {"workloads"} == {"name", "unit", "better", "bound", "source"}
        assert e["source"] in ("host_clock", "device_trace")
        assert 0.01 <= e["bound"] <= 0.1
    for p in MAN["per_layer"]:
        assert set(p) - {"workloads"} == {"name", "unit", "better", "source", "layer", "moves"}
    names = [x["name"] for x in METRICS]
    assert len(names) == len(set(names))
    assert len({(w["config"], w["traffic"]) for w in MAN["workloads"]}) == len(MAN["workloads"])
    assert "setup_s" in {e["name"] for e in MAN["end_to_end"]}


@pytest.mark.parametrize("cell", [w["name"] for w in MAN["workloads"]])
def test_every_cell_finds_its_files_and_reports_enough(cell):
    c = M.load_cell(cell)
    assert c.traffic_path.is_file()
    assert {"model", "server_model", "server_flags", "http_workers", "limits"} <= set(c.config)
    model, served = c.config["model"], c.config["server_model"]
    from benchmark.reference import nets
    assert callable(nets.load(model["network"]))
    assert served["input_size"] == [model["input_size"]] * 2 and served["zoo_classes"] == model["num_classes"]
    assert (served["zoo_width"], served["dtype"], served["topk"]) == (model["width"], model["dtype"], model["topk"])
    # the worker pool is stated first; what else departs from the server's defaults, the file says why
    assert c.config["server_flags"][:2] == ["--http-workers", str(c.config["http_workers"])]
    assert len(c.config["server_flags"]) == 2 or c.config["deployment"]
    e2e = {e["name"] for e in c.end_to_end}
    assert "setup_s" in e2e and len(e2e) >= 2
    assert c.per_layer
    for p in c.per_layer:
        assert p["moves"] in e2e, f"{p['name']} moves {p['moves']}, which {cell} does not report"
        read, args = M.load_reader(p["name"])
        assert callable(read)


def test_every_config_is_used_and_every_file_name_is_plain():
    used = {w["config"] for w in MAN["workloads"]}
    assert used == {c["name"] for c in MAN["configs"]}
    assert len({c["file"] for c in MAN["configs"]}) == len(MAN["configs"])
    for path in MAN["paths"]:
        for f in (ROOT / path).rglob("*"):
            if "__pycache__" in f.parts or f.suffix == ".pyc":
                continue
            assert re.fullmatch(r"[A-Za-z0-9_.\-/]+", str(f.relative_to(ROOT))), f


def test_a_later_pr_adds_a_config_a_mix_a_metric_and_a_cell_as_new_files(tmp_path):
    """Nothing that is there is edited: new files and manifest entries only."""
    bench = tmp_path / "benchmark"
    shutil.copytree(ROOT / "benchmark", bench, ignore=shutil.ignore_patterns("__pycache__"))
    before = {f: f.read_bytes() for f in bench.rglob("*") if f.is_file()}
    cfg = json.loads((tmp_path / MAN["configs"][0]["file"]).read_text())
    cfg["model"]["input_size"] = 192
    (bench / "reference" / "two_layers.py").write_text(
        "def two_layers(o, x, num_classes=1000, width=1.0):\n"
        "    return o.head('logits', o.conv_bn('stem', x, 8, (3, 3), 2), num_classes)\n")
    cfg["model"]["network"] = "benchmark/reference/two_layers.py::two_layers"
    (bench / "configs" / "mv2-192-bf16.json").write_text(json.dumps(cfg))
    mix = json.loads((bench / "traffic" / f"{MAN['workloads'][0]['traffic']}.json").read_text())
    mix["clients"] = 8
    (bench / "traffic" / "photos-half.json").write_text(json.dumps(mix))
    (bench / "readers" / "answered.py").write_text(
        "def read(ctx, scale=1):\n    return scale * len(ctx.outcomes) or None\n")
    (bench / "metrics" / "answered_requests.json").write_text(
        json.dumps({"reader": "answered", "args": {"scale": 2}}))
    man = json.loads(json.dumps(MAN))
    man["configs"].append({"name": "mv2-192-bf16", "source": "x", "reduced": [], "why": "y",
                           "file": "benchmark/configs/mv2-192-bf16.json"})
    man["workloads"].append({"name": "mv2-192-photos-half", "config": "mv2-192-bf16",
                             "traffic": "photos-half", "chips": 1, "why": "z"})
    for e in man["end_to_end"]:
        if e["name"] == "images_per_s":
            e["workloads"].append("mv2-192-photos-half")
    man["per_layer"].append({"name": "answered_requests", "unit": "count", "better": "higher",
                             "source": "host_clock", "layer": "load generator (benchmark)",
                             "moves": "images_per_s", "workloads": ["mv2-192-photos-half"]})
    cell = M.load_cell("mv2-192-photos-half", man, bench)
    assert cell.config["model"]["input_size"] == 192
    import importlib.util
    spec = importlib.util.spec_from_file_location("copied_nets", bench / "reference" / "nets.py")
    copied_nets = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(copied_nets)
    walked = copied_nets.walk(cell.config["model"]["network"], 192, 1000, 1.0)   # the copy finds its own new file
    assert walked.params["params/logits/kernel"] == (8, 1000) and walked.macs["stem"] == 96 * 96 * 27 * 8
    assert cell.traffic_path == bench / "traffic" / "photos-half.json"
    assert {e["name"] for e in cell.end_to_end} == {"images_per_s", "setup_s"}
    assert "answered_requests" in {p["name"] for p in cell.per_layer}
    read, args = M.load_reader("answered_requests", bench)
    from types import SimpleNamespace
    assert read(SimpleNamespace(outcomes=[1, 2, 3]), **args) == 6
    from benchmark import traffic
    assert traffic.Mix.load(cell.traffic_path).clients == 8
    assert all(f.read_bytes() == data for f, data in before.items())


def test_run_py_has_no_branch_on_a_cell_or_configuration_name():
    code = "".join((ROOT / "benchmark" / f).read_text()
                   for f in ("run.py", "loadgen.py", "traffic.py", "manifest.py", "check.py"))
    for entry in MAN["configs"] + MAN["workloads"]:
        assert entry["name"] not in code


@pytest.mark.parametrize("path", sorted((ROOT / "benchmark" / "metrics").glob("*.json")), ids=lambda p: p.stem)
def test_every_metric_file_names_a_reader_that_exists(path):
    assert M.NAME.match(path.stem)
    read, args = M.load_reader(path.stem)
    assert callable(read) and isinstance(args, dict)
