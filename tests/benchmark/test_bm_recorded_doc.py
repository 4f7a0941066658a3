"""A check document recorded on the chip (``data/iv3_check_doc.json``: the
three smallest photos of one run's sample, with what the server answered for
each) reads the same numbers through the named-child path as through
``check.py`` run directly, and near what the chip's own reference read."""

import json
import os
import subprocess
import sys
from pathlib import Path

import pytest

from benchmark import run as R

ROOT = Path(__file__).resolve().parents[2]
DOC = json.loads((Path(__file__).resolve().parent / "data" / "iv3_check_doc.json").read_text())
CONFIG = json.loads((ROOT / "benchmark" / "configs" / "iv3-299-bf16-4k.json").read_text())


def test_the_named_child_reads_what_check_py_reads(monkeypatch):
    assert DOC["model"] == CONFIG["model"] and DOC["limits"] == CONFIG["limits"] and "check" not in CONFIG
    doc = {k: DOC[k] for k in ("model", "seed", "limits", "items", "control")}
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    direct = subprocess.run([sys.executable, str(ROOT / "benchmark" / "check.py")], input=json.dumps(doc).encode(),
                            cwd=ROOT, env=env, capture_output=True, timeout=900)
    assert direct.returncode == 0, direct.stderr.decode()[-3000:]
    direct = json.loads(direct.stdout.decode().strip().splitlines()[-1])
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    named = R.check_child(CONFIG, DOC["seed"], DOC["items"], None, limit_s=900.0)
    assert named == direct and named["images"] == len(DOC["items"]) == 3
    assert list(named["compared"]) == ["logit_rms", "logit_max", "int8_weight_share"]
    # the reference on the CPU against the chip's: float32 both, the same served answers
    for name in ("logit_rms", "logit_max"):
        assert named["compared"][name]["value"] == pytest.approx(DOC["on_the_chip"]["compared"][name]["value"], rel=0.05)
        assert named["compared"][name]["limit"] == CONFIG["limits"][name]
