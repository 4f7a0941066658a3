#!/usr/bin/env python3
"""The weights of ``reference/brumby.py``'s model from ``--seed``, as the
``--ckpt`` export that the program reads leaf by leaf.

    python benchmark/reference/brumby_weights.py <the configuration's model block, as JSON> <seed> <dir>

To the contract of ``reference/__init__.py``, as ``nemotron_h_weights.py``
writes its model's: every leaf a function of ``(seed, leaf name)`` alone
(``brumby.make_leaf`` over ``leaves.py``), a leaf of more than 16 M values
in row blocks of its own streams (the embedding and the head are 778 M
values each, an FFN matrix 89 M), made and written a block at a time in the
dtype the configuration serves, in place over an export of the same shapes,
on threads. Plain numpy: nothing here touches a device.
"""

from __future__ import annotations

import json
import os
import shutil
import sys
from concurrent.futures import ThreadPoolExecutor
from pathlib import Path

import numpy as np

if __package__ in (None, ""):
    sys.path.insert(0, str(Path(__file__).resolve().parents[2]))

from benchmark.reference import brumby  # noqa: E402
from benchmark.reference.longcat_weights import DTYPES, file_of  # noqa: E402
from benchmark.reference.nemotron_h import blocks  # noqa: E402


def write_export(m: dict, seed: int, directory: Path, threads: int | None = None) -> None:
    shapes, dtype = brumby.all_leaves(m), np.dtype(DTYPES[m["dtype"]])
    wanted = {file_of(directory, leaf).name for leaf in shapes} | {"manifest.json"}
    if directory.is_dir() and {f.name for f in directory.iterdir()} != wanted:
        shutil.rmtree(directory)
    directory.mkdir(parents=True, exist_ok=True)
    for leaf, shape in shapes.items():      # every file at its size first: blocks are written at their offsets
        path, size = file_of(directory, leaf), int(np.prod(shape)) * dtype.itemsize
        if not (path.is_file() and path.stat().st_size == size):
            with open(path, "wb") as f:
                f.truncate(size)

    def one(unit: tuple[str, int]) -> None:
        leaf, block = unit
        shape = shapes[leaf]
        values = brumby.make_block(seed, leaf, shape, m, block).astype(dtype)
        with open(file_of(directory, leaf), "r+b") as f:
            f.seek(blocks(shape)[block][0] * int(np.prod(shape[1:])) * dtype.itemsize)
            f.write(values.reshape(-1).view(np.uint8).data)

    units = [(leaf, b) for leaf, shape in shapes.items() for b in range(len(blocks(shape)))]
    units.sort(key=lambda u: -int(np.prod(shapes[u[0]])) // len(blocks(shapes[u[0]])))   # the largest first
    with ThreadPoolExecutor(threads or os.cpu_count() or 4) as pool:
        list(pool.map(one, units))
    (directory / "manifest.json").write_text(json.dumps({"dtype": m["dtype"], "leaves": shapes}))


def read_leaf(m: dict, directory: Path, leaf: str) -> np.ndarray:
    """One leaf of an export, back in float32 (for the tests)."""
    shape = brumby.all_leaves(m)[leaf]
    return np.fromfile(file_of(directory, leaf), DTYPES[m["dtype"]]).reshape(shape).astype(np.float32)


def main(argv) -> int:
    model, seed, directory = argv
    write_export(json.loads(model), int(seed), Path(directory))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
