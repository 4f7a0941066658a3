"""One leaf of a model's weights from ``(seed, leaf name)`` alone.

A counter-based generator (Philox) keyed by the seed and by the leaf's name,
so that a weights script writes leaf after leaf without ever holding the
tree, and a check child makes any leaf again (one expert, one layer) without
the export and without drawing what comes before it. Plain numpy, on the
host. ``weights.py::make``, the conv classifiers' single PCG64 stream, is
older than this and keeps its numbers.
"""

from __future__ import annotations

import hashlib

import numpy as np


def generator(seed: int, name: str) -> np.random.Generator:
    """The stream of leaf ``name`` under ``seed`` (any whole number below 2**64)."""
    of_name = int.from_bytes(hashlib.sha256(name.encode()).digest()[:8], "little")
    return np.random.Generator(np.random.Philox(key=np.array([seed, of_name], np.uint64)))


def normal(seed: int, name: str, shape: tuple[int, ...], std: float) -> np.ndarray:
    """Leaf ``name`` as float32: centred normal values of deviation ``std``."""
    return generator(seed, name).standard_normal(shape, np.float32) * np.float32(std)
