#!/usr/bin/env python3
"""The weights of ``reference/longcat.py``'s model from ``--seed``, as the
``--ckpt`` export that the program reads leaf by leaf.

    python benchmark/reference/longcat_weights.py <the configuration's model block, as JSON> <seed> <dir>

To the contract of ``reference/__init__.py``: every leaf is a function of
``(seed, leaf name)`` alone (``longcat.make_leaf`` over ``leaves.normal``),
made and written one at a time in the dtype the configuration serves, as
``<dir>/<leaf name with / as .>`` (raw values, row-major) beside
``manifest.json`` (``dtype`` and every leaf's shape). Threads over leaves:
5.2 G values at 20 M a second a core want every core (numpy's generators
and ``tofile`` release the interpreter lock); a thread holds one leaf, 0.4
GB in float32 at most. Plain numpy: nothing here touches a device.
"""

from __future__ import annotations

import json
import os
import shutil
import sys
from concurrent.futures import ThreadPoolExecutor
from pathlib import Path

import ml_dtypes
import numpy as np

if __package__ in (None, ""):
    sys.path.insert(0, str(Path(__file__).resolve().parents[2]))

from benchmark.reference import longcat  # noqa: E402

DTYPES = {"bfloat16": ml_dtypes.bfloat16, "float32": np.float32}   # what an export may hold


def file_of(directory: Path, leaf: str) -> Path:
    return directory / leaf.replace("/", ".")


def write_export(m: dict, seed: int, directory: Path, threads: int | None = None) -> None:
    """Over an export of the same shapes (the run before, another seed) each
    leaf is written in place, into the blocks its file already has: a machine
    whose disk counts every block ever written (the chip tool's ended a call
    at 45 GiB, after four fresh 10.4 GB exports) then sees one export's
    worth however many runs follow one another."""
    shapes, dtype = longcat.all_leaves(m), DTYPES[m["dtype"]]
    wanted = {file_of(directory, leaf).name for leaf in shapes} | {"manifest.json"}
    if directory.is_dir() and {f.name for f in directory.iterdir()} != wanted:
        shutil.rmtree(directory)
    directory.mkdir(parents=True, exist_ok=True)

    def one(leaf: str) -> None:
        values, path = longcat.make_leaf(seed, leaf, shapes[leaf], m).astype(dtype), file_of(directory, leaf)
        in_place = path.is_file() and path.stat().st_size == values.nbytes
        with open(path, "r+b" if in_place else "wb") as f:
            f.write(values.reshape(-1).view(np.uint8).data)

    with ThreadPoolExecutor(threads or os.cpu_count() or 4) as pool:
        # the largest first, so that no thread is left alone with one at the end
        list(pool.map(one, sorted(shapes, key=lambda n: -int(np.prod(shapes[n])))))
    (directory / "manifest.json").write_text(json.dumps({"dtype": m["dtype"], "leaves": shapes}))


def read_leaf(m: dict, directory: Path, leaf: str) -> np.ndarray:
    """One leaf of an export, back in float32 (for the tests)."""
    shape = longcat.all_leaves(m)[leaf]
    return np.fromfile(file_of(directory, leaf), DTYPES[m["dtype"]]).reshape(shape).astype(np.float32)


def main(argv) -> int:
    model, seed, directory = argv
    write_export(json.loads(model), int(seed), Path(directory))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
