"""Floors of a convolutional classifier behind the resize: the default ``floors.module``.

What ``cost.py`` asks of a floors module, for one row of ``/stats``'s
padding table over the window (``readers/_stats.py::buckets``: ``canvas``,
``batch_bucket``, ``batches``, ``rows_real``, ``rows_dispatched``,
``px_real``): :func:`image_flops` and :func:`serve_bytes`. Here every image
is resized to one square, so neither the canvas nor the real pixels change
the model's work:

- the model's multiply-adds at its input size, twice, per *real* image (a
  padded row of a batch bucket is avoidable);
- a bilinear resize's 8 operations per output value (four taps). The
  program's matmul resize does ``h*s*s*3 + h*w*s*3`` multiply-adds per image
  (:func:`matmul_resize_flops`, a copy of ``costmodel.preprocess_flops``),
  which a gather or a fused kernel avoids, so it is not in a floor;
- bytes: the parameters once per call in the serving dtype, each real
  image's canvas read once as uint8, the top-k written. Activations are not
  counted: a fused implementation keeps them on chip.

The counts come from the benchmark's own walker (``nets.py::ShapeOps``; a
test holds them equal to ``serving/costmodel.py``'s).
"""

from __future__ import annotations

from functools import lru_cache

from benchmark.reference import nets

DTYPE_BYTES = {"bfloat16": 2, "float32": 4}


@lru_cache(maxsize=None)
def model_macs(network: str, input_size: int, num_classes: int, width: float) -> int:
    return sum(nets.walk(network, input_size, num_classes, width).macs.values())


@lru_cache(maxsize=None)
def param_count(network: str, input_size: int, num_classes: int, width: float, with_stats: bool = True) -> int:
    """Parameter scalars. ``with_stats=False`` leaves out batch-norm means
    and variances (flax's ``params`` collection alone, as costmodel counts)."""
    shapes = nets.walk(network, input_size, num_classes, width).params
    total = 0
    for name, shape in shapes.items():
        if with_stats or not name.startswith("batch_stats/"):
            n = 1
            for d in shape:
                n *= d
            total += n
    return total


def matmul_resize_flops(canvas_s: int, input_size: int) -> int:
    """What the program's separable matmul resize executes per image: not a floor."""
    h = w = input_size
    return 2 * (h * canvas_s * canvas_s * 3 + h * w * canvas_s * 3)


def resize_floor_flops(input_size: int) -> int:
    return 8 * input_size * input_size * 3


def image_flops(model: dict, row: dict) -> int:
    """Floor operations of one real image of ``row``: the forward pass and
    the resize, the same in every row."""
    macs = model_macs(model["network"], model["input_size"], model["num_classes"], model["width"])
    return 2 * macs + resize_floor_flops(model["input_size"])


def serve_bytes(model: dict, row: dict) -> float:
    """Bytes that the mean serve call of ``row`` must move."""
    rows_real = row["rows_real"] / row["batches"]
    params = param_count(model["network"], model["input_size"], model["num_classes"], model["width"])
    return (params * DTYPE_BYTES[model["dtype"]] + rows_real * row["canvas"] * row["canvas"] * 3
            + rows_real * model["topk"] * 8)
