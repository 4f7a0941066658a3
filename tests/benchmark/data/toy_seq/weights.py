#!/usr/bin/env python3
"""The toy's weights script, to the contract of ``benchmark/reference``:
``<model block as JSON> <seed> <directory>``; every leaf from ``(seed, leaf
name)``, made and written one at a time as raw values of the served dtype
in ``<directory>/<leaf name with / as .>``."""

import json
import sys
from pathlib import Path

sys.path[:0] = [str(Path(__file__).resolve().parents[4]), str(Path(__file__).resolve().parent)]

import net  # noqa: E402
from benchmark.reference import leaves  # noqa: E402


def main(argv) -> int:
    model, seed, directory = json.loads(argv[0]), int(argv[1]), Path(argv[2])
    directory.mkdir(parents=True, exist_ok=True)
    for name, shape in net.shapes(model).items():
        leaf = leaves.normal(seed, name, shape, net.std(name, shape))
        leaf.astype(net.DTYPES[model["dtype"]]).tofile(directory / name.replace("/", "."))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
