"""What the device's programs have to do at least: operations and bytes.

The numerators of every roofline share and of ``step_mfu``. A floor counts
only what no implementation can avoid. What that is for a serve call is the
architecture's to say, so it comes from the module that the configuration
names (``floors.module``, default ``reference/conv_floors.py``), which
answers two questions about one row of the window's padding table
(``readers/_stats.py::buckets``: a canvas and batch bucket with its
batches, real rows and real pixels):

    image_flops(model, row)   floor operations of one real image of that row
    serve_bytes(model, row)   bytes the row's mean serve call must move
                              (parameters touched, inputs, answers)

A classifier behind a resize answers the same for every row; a decoder's
operations go with the row's real pixels (its tokens), its attention with
their square, its experts' bytes with how many a batch touches. The peaks
(``peaks.py``) and the ragged unpack's floor are the same for every
architecture and stay here: the tight decoded bytes read once and the
canvases written once.
"""

from __future__ import annotations

from functools import lru_cache
from pathlib import Path

from .manifest import load_module, named


@lru_cache(maxsize=None)
def _floors_at(path: Path):
    return load_module(path, "floors")


def load_floors(config: dict):
    """The floors module that ``config`` names."""
    return _floors_at(named(config).floors)


def serve_floor_s(floors, model: dict, row: dict, peak_flops: float, peak_bytes: float) -> tuple[float, str]:
    """Least seconds for the mean serve program call of ``row`` (its real
    images a batch on its canvas), and which peak binds."""
    flops = row["rows_real"] / row["batches"] * floors.image_flops(model, row)
    t_flops, t_bytes = flops / peak_flops, floors.serve_bytes(model, row) / peak_bytes
    return (t_flops, "compute") if t_flops >= t_bytes else (t_bytes, "bandwidth")


def step_flops(floors, model: dict, rows: list[dict]) -> float:
    """Floor operations of the mean real image over ``rows``."""
    return sum(r["rows_real"] * floors.image_flops(model, r) for r in rows) / sum(r["rows_real"] for r in rows)


def unpack_floor_s(tight_bytes: float, canvas_bytes_written: float, peak_bytes: float) -> float:
    """Least seconds to turn tight decoded rows into canvases: every tight
    byte read once, every canvas byte written once. Bandwidth binds: the
    unpack does no arithmetic."""
    return (tight_bytes + canvas_bytes_written) / peak_bytes
