#!/usr/bin/env python3
"""The control's readings, on the chip, with no server: the reference in 8 bits
against the reference in float32, on images and weights drawn from each seed.

    python3 benchmark/control.py --workload <cell> --seeds 1,2,3 [--controls int8_weights,fp8_e5m2]

One line per seed and control with the numbers the configuration's check
child compares (``check.py`` unless the configuration names another). The
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
import sys
from pathlib import Path

if __package__ in (None, ""):
    sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

from benchmark import traffic  # noqa: E402
from benchmark.manifest import load_cell, named  # noqa: E402
from benchmark.run import check_child  # noqa: E402


def main(argv=None) -> int:
    p = argparse.ArgumentParser()
    p.add_argument("--workload", required=True)
    p.add_argument("--seeds", required=True)
    p.add_argument("--controls", default="int8_weights,fp8_e5m2")
    args = p.parse_args(argv)
    cell = load_cell(args.workload)
    sample_images = named(cell.config).sample_images
    mix = traffic.Mix.load(cell.traffic_path)
    for seed in [int(s) for s in args.seeds.split(",")]:
        source = traffic.Source(traffic.Corpus(mix, seed), seed)
        images = []
        while len(images) < sample_images:
            images += source.take().images
        items = [{"jpeg": base64.b64encode(traffic.variant(b, k)).decode(), "served": []}
                 for b, k in images[:sample_images]]
        for control in args.controls.split(","):
            out = check_child(cell.config, seed, items, control, limit_s=600.0)   # no run of the benchmark: no run's limit
            print(json.dumps({"workload": args.workload, "seed": seed, "control": control, **out}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
