"""What the device's programs have to do at least: operations and bytes.

The numerators of every roofline share and of ``step_mfu``, computed from
shapes by the benchmark's own walkers (``reference/nets.py::ShapeOps``; a
test holds their counts equal to ``serving/costmodel.py``'s). A floor counts
only what no implementation can avoid:

- the model's multiply-adds at its input size, twice, per *real* image (a
  padded row of a batch bucket is avoidable);
- a bilinear resize's 8 operations per output value (four taps). The
  program's matmul resize does ``h*s*s*3 + h*w*s*3`` multiply-adds per image
  (:func:`matmul_resize_flops`, a copy of ``costmodel.preprocess_flops``),
  which a gather or a fused kernel avoids, so it is not in a floor;
- bytes: the parameters once per batch in the serving dtype, each real
  image's canvas read once as uint8, the top-k written; for the ragged
  unpack the tight decoded bytes read once and the canvases written once.
  Activations are not counted: a fused implementation keeps them on chip.
"""

from __future__ import annotations

from functools import lru_cache

from .reference import nets

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


def image_flops(model: dict) -> int:
    """Floor operations per real image: the forward pass and the resize."""
    macs = model_macs(model["network"], model["input_size"], model["num_classes"], model["width"])
    return 2 * macs + resize_floor_flops(model["input_size"])


def serve_floor_s(model: dict, canvas_s: int, rows_real: float, peak_flops: float,
                  peak_bytes: float) -> tuple[float, str]:
    """Least seconds for one serve program call with ``rows_real`` real
    images on ``canvas_s`` canvases, and which peak binds."""
    flops = rows_real * image_flops(model)
    params = param_count(model["network"], model["input_size"], model["num_classes"], model["width"])
    nbytes = (params * DTYPE_BYTES[model["dtype"]] + rows_real * canvas_s * canvas_s * 3
              + rows_real * model["topk"] * 8)
    t_flops, t_bytes = flops / peak_flops, nbytes / peak_bytes
    return (t_flops, "compute") if t_flops >= t_bytes else (t_bytes, "bandwidth")


def unpack_floor_s(tight_bytes: float, canvas_bytes_written: float, peak_bytes: float) -> float:
    """Least seconds to turn tight decoded rows into canvases: every tight
    byte read once, every canvas byte written once. Bandwidth binds: the
    unpack does no arithmetic."""
    return (tight_bytes + canvas_bytes_written) / peak_bytes
