"""``BENCHMARK.json`` and the files it names: found by name, never by branch.

A cell names a configuration and a traffic mix; a per-layer metric names
itself. Each has one file of its own:

    benchmark/configs/<config>.json    the model's sizes and the server's flags
    benchmark/traffic/<traffic>.json   the mix's parameters (``traffic.py`` reads it)
    benchmark/metrics/<metric>.json    {"reader": <module in readers/>, "args": {...}}

so a later PR adds a cell, a mix, a configuration or a metric as new files
plus a manifest entry, and edits nothing that is here.
"""

from __future__ import annotations

import importlib.util
import json
import re
from dataclasses import dataclass
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent
BENCH = Path(__file__).resolve().parent
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")


@dataclass(frozen=True)
class Cell:
    name: str
    chips: int
    config_name: str
    config: dict          # benchmark/configs/<config>.json
    traffic_name: str
    traffic_path: Path
    end_to_end: tuple[dict, ...]   # the manifest's entries this cell reports
    per_layer: tuple[dict, ...]


def load_manifest(path: Path | None = None) -> dict:
    m = json.loads((path or ROOT / "BENCHMARK.json").read_text())
    for key in ("command", "paths", "run_seconds", "configs", "workloads", "end_to_end", "per_layer"):
        if key not in m:
            raise ValueError(f"BENCHMARK.json lacks {key!r}")
    return m


def _reports(metric: dict, cell: str, e2e_of_cell: set[str] | None) -> bool:
    """Does ``cell`` report ``metric``? By its ``workloads`` list where it
    has one; else (per-layer) wherever the end-to-end metric it moves is."""
    if "workloads" in metric:
        return cell in metric["workloads"]
    return e2e_of_cell is None or metric["moves"] in e2e_of_cell


def load_cell(name: str, manifest: dict | None = None, bench_dir: Path = BENCH) -> Cell:
    m = manifest or load_manifest()
    try:
        w = next(w for w in m["workloads"] if w["name"] == name)
    except StopIteration:
        raise KeyError(f"no workload {name!r}; have {[w['name'] for w in m['workloads']]}") from None
    cfg_entry = next(c for c in m["configs"] if c["name"] == w["config"])
    config = json.loads((bench_dir.parent / cfg_entry["file"]).read_text())
    traffic_path = bench_dir / "traffic" / f"{w['traffic']}.json"
    if not traffic_path.is_file():
        raise FileNotFoundError(f"cell {name}: no traffic file {traffic_path}")
    e2e = tuple(e for e in m["end_to_end"] if _reports(e, name, None))
    names = {e["name"] for e in e2e}
    per_layer = tuple(p for p in m["per_layer"] if _reports(p, name, names))
    return Cell(name, int(w["chips"]), w["config"], config, w["traffic"], traffic_path, e2e, per_layer)


def load_reader(metric: str, bench_dir: Path = BENCH):
    """(read function, args) for a per-layer metric: its file names a module
    of ``readers/`` whose ``read(ctx, **args)`` returns a number, or None
    where it finds nothing to read."""
    spec = json.loads((bench_dir / "metrics" / f"{metric}.json").read_text())
    path = bench_dir / "readers" / f"{spec['reader']}.py"
    mod_spec = importlib.util.spec_from_file_location(f"benchmark_reader_{spec['reader']}", path)
    mod = importlib.util.module_from_spec(mod_spec)
    mod_spec.loader.exec_module(mod)
    return mod.read, spec.get("args", {})
