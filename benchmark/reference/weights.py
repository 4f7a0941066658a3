"""Weights from ``--seed``: the same numbers for the server and for the reference.

``server.py`` has no seed flag, so the benchmark makes the weights itself
and hands them to the server as a ``--ckpt`` serving export; the reference
calls :func:`make` with the same seed and never reads that export. Plain
numpy on the host: 24 M values take under a second, and the process that
writes the export must stay off the chip (the server child needs it).

    python benchmark/reference/weights.py <the configuration's model block, as JSON> <seed> <dir>

writes the export with orbax (the format ``--ckpt`` reads). It runs as a
child with ``JAX_PLATFORMS=cpu``: orbax imports JAX. This is the default
``weights.script`` (``manifest.py::named``); the package's docstring has
what a configuration's own script keeps to, and where this one does not.
"""

from __future__ import annotations

import json
import sys
from pathlib import Path

import numpy as np

if __package__ in (None, ""):
    sys.path.insert(0, str(Path(__file__).resolve().parents[2]))

from benchmark.reference import nets  # noqa: E402

# The dense layer's gain over unit-variance logits: they spread over a few
# units, so that the top scores are neither uniform (1/1000 each, where top-k
# is a coin toss) nor one-hot (where no error shows).
HEAD_GAIN = 3.0


def make(network: str, input_size: int, num_classes: int, width: float, seed: int) -> dict[str, np.ndarray]:
    """Every parameter of ``network`` (``nets.load``) as float32, keyed by checkpoint name.

    A kernel's variance is 1 / (fan-in x the mean square of its layer's
    input) (``nets.ShapeOps.in_moment``: He's rule after a rectifier, half
    of it after a linear layer or a residual sum), so every layer's output
    has about unit variance; the batch-norm statistics and affines are drawn
    away from the identity (scale and variance in [0.8, 1.25], mean and bias
    +-0.1), so that a path that drops or misfolds them reads wrong."""
    rs = np.random.Generator(np.random.PCG64(seed))
    out = {}
    walked = nets.walk(network, input_size, num_classes, width)
    for name, shape in walked.params.items():
        leaf = name.rsplit("/", 1)[1]
        if leaf == "kernel":
            fan_in = int(np.prod(shape[:-1]))
            gain = (HEAD_GAIN if len(shape) == 2 else 1.0) / walked.in_moment[name]
            v = rs.standard_normal(shape, np.float32) * np.float32(np.sqrt(gain / fan_in))
        elif leaf in ("scale", "var"):
            v = rs.uniform(0.8, 1.25, shape).astype(np.float32)
        else:  # BN mean and bias, dense bias
            v = rs.normal(0.0, 0.1, shape).astype(np.float32)
        out[name] = v
    return out


def nest(flat: dict[str, np.ndarray]) -> dict:
    tree: dict = {}
    for name, v in flat.items():
        node = tree
        *path, leaf = name.split("/")
        for part in path:
            node = node.setdefault(part, {})
        node[leaf] = v
    return tree


def write_export(flat: dict[str, np.ndarray], directory: str) -> None:
    """A serving export as ``tools/train.py`` writes one: an orbax
    checkpoint that holds exactly ``params`` and ``batch_stats``."""
    import shutil

    import orbax.checkpoint as ocp

    shutil.rmtree(directory, ignore_errors=True)
    mngr = ocp.CheckpointManager(
        directory, options=ocp.CheckpointManagerOptions(max_to_keep=1, create=True))
    try:
        mngr.save(0, args=ocp.args.StandardSave(nest(flat)))
        mngr.wait_until_finished()
    finally:
        mngr.close()


def main(argv) -> int:
    model, seed, directory = argv
    m = json.loads(model)
    write_export(make(m["network"], int(m["input_size"]), int(m["num_classes"]), float(m["width"]), int(seed)),
                 directory)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
