#!/usr/bin/env python3
"""The control's readings, on the chip, with no server: the reference in 8 bits
against the reference in float32, on images and weights drawn from each seed.

    python3 benchmark/control.py --workload <cell> --seeds 1,2,3 [--controls int8_weights,fp8_e5m2]

One line per seed and control with the numbers ``check.py`` compares. The
benchmark's own runs never call this; a ``benchmark`` PR does, when it sets
or re-reads a limit (the upper reading is the smallest that the control
gives; the lower one comes from the runs' own ``compared`` values). The
program's own ``dtype=int8`` tier, the control that has a server to boot,
is read with ``probe.py --serve-dtype int8``.
"""

from __future__ import annotations

import argparse
import base64
import json
import subprocess
import sys
from pathlib import Path

if __package__ in (None, ""):
    sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

from benchmark import traffic  # noqa: E402
from benchmark.manifest import BENCH, ROOT, load_cell  # noqa: E402
from benchmark.run import SAMPLE_IMAGES, child_env  # noqa: E402


def main(argv=None) -> int:
    p = argparse.ArgumentParser()
    p.add_argument("--workload", required=True)
    p.add_argument("--seeds", required=True)
    p.add_argument("--controls", default="int8_weights,fp8_e5m2")
    args = p.parse_args(argv)
    cell = load_cell(args.workload)
    mix = traffic.Mix.load(cell.traffic_path)
    for seed in [int(s) for s in args.seeds.split(",")]:
        source = traffic.Source(traffic.Corpus(mix, seed), seed)
        images = []
        while len(images) < SAMPLE_IMAGES:
            images += source.take().images
        items = [{"jpeg": base64.b64encode(traffic.variant(b, k)).decode(), "served": []}
                 for b, k in images[:SAMPLE_IMAGES]]
        for control in args.controls.split(","):
            doc = {"model": cell.config["model"], "seed": seed, "limits": cell.config["limits"],
                   "items": items, "control": control}
            proc = subprocess.run([sys.executable, str(BENCH / "check.py")], input=json.dumps(doc).encode(),
                                  cwd=ROOT, env=child_env(), capture_output=True, timeout=600)
            if proc.returncode != 0:
                print(proc.stderr.decode(errors="replace")[-2000:], file=sys.stderr)
                return 1
            out = json.loads(proc.stdout.decode().strip().splitlines()[-1])
            print(json.dumps({"workload": args.workload, "seed": seed, "control": control, **out}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
