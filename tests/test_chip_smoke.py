"""chip_smoke.py: the one-process rule, the seeded images, the last line —
and, marked slow, the whole CPU rehearsal.

The script proves on every later tree that the serving path starts and
answers on the chip. Its own process must stay off JAX (a parent that has
touched JAX holds the chip, and the server child then fails or hangs), its
images must be the same in every run, and a run that did not see a TPU must
never read as a pass.
"""

import json
import os
import subprocess
import sys
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parent.parent


def test_importing_chip_smoke_does_not_import_jax():
    # A fresh interpreter: this pytest process imported jax long ago.
    code = ("import sys; sys.path.insert(0, %r); import chip_smoke; "
            "chip_smoke.seeded_jpeg(0, 8, 8); "
            "print(sorted(m for m in sys.modules if m == 'jax' or m.startswith('jax.')))"
            % str(REPO))
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, timeout=120)
    assert out.returncode == 0, out.stderr[-2000:]
    assert out.stdout.strip() == "[]"


def test_seeded_jpeg_is_deterministic_and_seeded():
    import chip_smoke

    a = chip_smoke.seeded_jpeg(7, 40, 56)
    assert a[:2] == b"\xff\xd8" and a == chip_smoke.seeded_jpeg(7, 40, 56)
    assert a != chip_smoke.seeded_jpeg(8, 40, 56)


@pytest.mark.parametrize("failures,platform,ok", [
    ([], "tpu", True),
    ([], "cpu", False),  # every phase passed — on the wrong platform
    (["main: /healthz ok"], "tpu", False),
    ([], None, False),  # no server ever answered /healthz
])
def test_last_line_passes_only_on_a_tpu_with_no_failure(failures, platform, ok):
    import chip_smoke

    device = platform and {"platform": platform, "kind": "k", "count": 1}
    line, code = chip_smoke.last_line(failures, device)
    doc = json.loads(line)
    assert doc["ok"] is ok and (code == 0) is ok
    assert set(doc) == {"ok", "device"}
    assert set(doc["device"]) == {"platform", "kind", "count"}


def test_parity_tolerance_is_the_engines():
    import chip_smoke
    from tensorflow_web_deploy_tpu.serving.engine import InferenceEngine

    tol = InferenceEngine._PARITY_TOL["bfloat16"]
    assert chip_smoke.PARITY_TOL == {"prob": tol["prob"], "topk": tol["topk"]}


def test_compare_topk_bounds():
    import chip_smoke

    top = lambda *pairs: [{"index": i, "score": s} for i, s in pairs]
    ref = top((1, 0.5), (2, 0.3), (3, 0.1))
    assert chip_smoke.compare_topk(ref, ref, 0.08) == (0.0, 1.0)
    # A near-tie swapped out of the list: tiny provable delta.
    got = top((1, 0.5), (2, 0.3), (4, 0.1))
    delta, agree = chip_smoke.compare_topk(ref, got, 0.08)
    assert delta == pytest.approx(0.0) and agree == pytest.approx(2 / 3)
    # A real disagreement: class 9 scores 0.9 where the reference has it
    # below its third best.
    delta, _ = chip_smoke.compare_topk(ref, top((9, 0.9), (1, 0.05), (2, 0.03)), 0.08)
    assert delta == pytest.approx(0.8)


def test_without_the_repo_beside_it_the_script_fails_and_prints_no_result(tmp_path):
    (tmp_path / "chip_smoke.py").write_bytes((REPO / "chip_smoke.py").read_bytes())
    out = subprocess.run([sys.executable, "chip_smoke.py"], cwd=tmp_path,
                         capture_output=True, text=True, timeout=120)
    assert out.returncode != 0 and out.stdout.strip() == ""


@pytest.mark.slow  # boots a server five times: ~3 min on the CPU
def test_cpu_rehearsal_runs_every_phase_and_is_not_a_pass():
    """on-chip-measurement guide §2.1: the command end to end at a tiny
    size under JAX_PLATFORMS=cpu — every check of every phase passes, and
    the last line still says ok: false, platform: cpu."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run([sys.executable, str(REPO / "chip_smoke.py"), "--rehearse"],
                         capture_output=True, text=True, timeout=1500, env=env)
    lines = [json.loads(ln) for ln in out.stdout.splitlines() if ln.startswith("{")]
    failed = [ln for ln in lines if ln.get("ok") is False and "check" in ln]
    assert not failed, (failed, out.stderr[-3000:])
    assert lines[-1] == {"ok": False,
                         "device": {"platform": "cpu", "kind": "cpu", "count": 1}}
    assert out.returncode != 0
    phases = {ln["phase"] for ln in lines if "phase" in ln}
    assert {"main", "restart", "kernels", "kernels-control"} <= phases
