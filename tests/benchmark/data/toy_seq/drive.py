#!/usr/bin/env python3
"""The toy through the harness, with a stand-in for the server: the weights
script's export is read back and answers a few images of a traffic mix; the
harness's ``run_check`` then judges those answers, and the ``roofline``
reader reads a trace made up to run at the floor. Run from the root of a
checkout (``python tests/benchmark/data/toy_seq/drive.py <cell> <seed>
<export directory> [<fault>]``); prints one JSON object. ``fault`` alters
the answers where they are produced: ``int8:<leaf>`` serves one leaf from
int8, ``permuted:<step>`` rotates one step's scores over its ids."""

import base64
import json
import sys
from pathlib import Path
from types import SimpleNamespace

import numpy as np

sys.path[:0] = [str(Path.cwd()), str(Path(__file__).resolve().parent)]

import net  # noqa: E402
from benchmark import check, cost, manifest, run, traffic  # noqa: E402


def export_leaves(model: dict, directory: Path) -> dict:
    return {name: np.fromfile(directory / name.replace("/", "."), net.DTYPES[model["dtype"]]).reshape(shape).astype(np.float32)
            for name, shape in net.shapes(model).items()}


def served_sample(cell, seed: int, params: dict, fault: str = ""):
    """(sample, requests) as ``run.py::draw_sample`` hands them to ``run_check``."""
    kind, _, what = fault.partition(":")
    if kind == "int8":
        params = {**params, what: net.stored(params[what], "int8")}
    m = cell.config["model"]
    source = traffic.Source(traffic.Corpus(traffic.Mix.load(cell.traffic_path), seed, threads=2), seed)
    sample, requests = [], {}
    for _ in range(manifest.named(cell.config).sample_images):
        req = source.take()
        jpeg = traffic.variant(*req.images[0])
        answers = net.answer(params, net.patches(check.pixels({"jpeg": base64.b64encode(jpeg)})),
                             m["answer_steps"], m["topk"])
        if kind == "permuted":
            step = answers[int(what)]
            answers[int(what)] = [[c, s] for (c, _), (_, s) in zip(step, step[1:] + step[:1])]
        requests[req.index] = req
        sample.append((SimpleNamespace(index=req.index, answers=[answers]), 0))
    return sample, requests


def main(argv) -> int:
    cell = manifest.load_cell(argv[0])
    seed, export = int(argv[1]), Path(argv[2])
    writer = run.write_weights(cell.config, seed, export)
    _, err = writer.communicate(timeout=120)
    if writer.returncode != 0:
        print(err.decode(), file=sys.stderr)
        return 1
    sample, requests = served_sample(cell, seed, export_leaves(cell.config["model"], export), *argv[3:4])
    out = run.run_check(cell, seed, sample, requests)
    # one bucket row, and a serve program traced at exactly that row's floor
    row = {"canvas": 128, "batch_bucket": 8, "batches": 4, "rows_real": 24, "rows_dispatched": 32,
           "px_real": 24 * 96 * 120}
    floors = cost.load_floors(cell.config)
    floor_s = cost.serve_floor_s(floors, cell.config["model"], row, 197e12, 819e9)[0]
    pad = {"128x8": row}
    ctx = SimpleNamespace(before={"batcher": {"builders": {"padding": {"128x8": dict.fromkeys(row, 0)}}}},
                          after={"batcher": {"builders": {"padding": pad}}}, config=cell.config,
                          device={"kind": "TPU v5 lite"},
                          trace={"programs": [["jit_serve", 4 * floor_s, 4]], "busy_s": 1.0, "window_s": 2.0})
    read, args = manifest.load_reader("serve_roofline")
    print(json.dumps({**out, "root": str(manifest.ROOT), "floors": floors.__file__,
                      "serve_roofline": read(ctx, **args), "image_flops": floors.image_flops(cell.config["model"], row)}))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
