"""``BENCHMARK.json`` and the files it names: found by name, never by branch.

A cell names a configuration and a traffic mix; a per-layer metric names
itself. Each has one file of its own:

    benchmark/configs/<config>.json    the model's sizes and the server's flags
    benchmark/traffic/<traffic>.json   the mix's parameters (``traffic.py`` reads it)
    benchmark/metrics/<metric>.json    {"reader": <module in readers/>, "args": {...}}

so a later PR adds a cell, a mix, a configuration or a metric as new files
plus a manifest entry, and edits nothing that is here.

A configuration's file also names the three pieces of the harness that know
its architecture (:func:`named`; ``reference/__init__.py`` has the contract
each keeps), as paths relative to the checkout, under ``paths``:

    weights.script   writes the served weights from the seed     benchmark/reference/weights.py
    check.child      decides ``correct`` from the plain reference benchmark/check.py
    floors.module    a real image's floor operations, a call's bytes  benchmark/reference/conv_floors.py

and three sizes: ``check.sample_images`` (128) and ``check.limit_s`` (240)
for the check, ``model.answer_steps`` (1) for how many top-k lists an image's
answer holds. A key left out means the default beside it: a convolutional
classifier behind the resize, one step an image.
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


@dataclass(frozen=True)
class Named:
    weights: Path          # ``weights.script``
    check: Path            # ``check.child``
    floors: Path           # ``floors.module``
    sample_images: int     # ``check.sample_images``
    limit_s: float         # ``check.limit_s``
    answer_steps: int      # ``model.answer_steps``


def named(config: dict, root: Path = ROOT) -> Named:
    """What a configuration's file names, defaults filled in. A named file
    that is not there is an error here, before anything boots."""
    check = config.get("check", {})
    files = {"weights": config.get("weights", {}).get("script", "benchmark/reference/weights.py"),
             "check": check.get("child", "benchmark/check.py"),
             "floors": config.get("floors", {}).get("module", "benchmark/reference/conv_floors.py")}
    for what, rel in files.items():
        if not (root / rel).is_file():
            raise FileNotFoundError(f"the configuration names {rel!r} as its {what}: no such file under {root}")
    return Named(**{what: root / rel for what, rel in files.items()},
                 sample_images=int(check.get("sample_images", 128)), limit_s=float(check.get("limit_s", 240.0)),
                 answer_steps=int(config["model"].get("answer_steps", 1)))


def load_module(path: Path, tag: str):
    """A module by its file: readers and floors are found so."""
    spec = importlib.util.spec_from_file_location(f"benchmark_{tag}_{path.stem}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


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
    named(config, bench_dir.parent)   # the weights' script, the check child and the floors it names exist
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
    return load_module(bench_dir / "readers" / f"{spec['reader']}.py", "reader").read, spec.get("args", {})
