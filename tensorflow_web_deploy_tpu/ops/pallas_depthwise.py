"""Mosaic (Pallas-TPU) fused depthwise-conv + BN-affine + relu6 kernel.

One grid program per (image, row tile): a tile of the pre-padded input,
the BN-folded kernel taps, and the bias all live in VMEM, and the kh·kw
shift-multiply-accumulate + affine + clamp happens in ONE pass — the
depthwise stack's activations never round-trip through HBM between the
conv, the BatchNorm, and the activation the way the unfused three-op chain
does. Stride-1 only (every MobileNetV2 stride-2 dw layer takes the XLA
shift-MAC in ops/depthwise.py, which dispatches per-layer).

Contract with ops/depthwise.py::fused_depthwise_bn — the only caller:

* the input arrives ALREADY padded (XLA pads; the kernel does static
  slices only, the strong preference on Mosaic);
* the kernel taps arrive BN-folded and flattened to [kh·kw, C] (2D, so
  the channel axis rides the 128-lane dim);
* the bias arrives as [1, C] (scalar-per-channel rows must be ≥2D);
* accumulation is f32 regardless of the serve dtype — the caller casts in
  and out (same two-step-cast discipline as the preprocess kernel).

Row tiling: in VMEM a block's two minor dims pad to (8, 128) f32 tiles, so
a whole 114×114×32 padded image is ~7 MB — 25.6 MB scoped with the output
and the pipeline's double buffers against the 16 MB limit on a v5e. The
grid therefore walks row tiles of ``th`` output rows (:func:`row_tile`:
the largest divisor of the output height whose working set fits
``_VMEM_BUDGET``). A tile needs ``th + kh − 1`` input rows; the ``kh − 1``
halo rows below the tile arrive as one-row blocks of the same input array,
so every block is a plain Blocked BlockSpec that the pipeline prefetches
(a one-row block can start at any row; a taller halo block could not).

``interpret=True`` runs the same kernel through the Pallas interpreter on
CPU — how tests/test_quant.py pins the kernel's semantics without TPU
hardware. What Mosaic accepts is pinned by tests/test_tpu_compile.py,
which compiles the kernel for a described v5e at the serving shapes.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# Bytes of VMEM one grid step may plan for: double-buffered input and
# output blocks, the assembled input window, and the accumulator with one
# tap temporary. Half the 16 MB scoped limit of a v5e; the rest is the
# compiler's own.
_VMEM_BUDGET = 8 << 20


def _tile_bytes(rows: int, w: int, c: int) -> int:
    """f32 VMEM footprint of a [rows, w, c] block: (w, c) pad to (8, 128)."""
    return rows * (-(-w // 8) * 8) * (-(-c // 128) * 128) * 4


def row_tile(oh: int, wp: int, ow: int, c: int, kh: int) -> int:
    """Output rows per grid step: the largest divisor of ``oh`` whose
    working set fits ``_VMEM_BUDGET`` (1 always divides, so this always
    answers)."""
    for th in range(oh, 0, -1):
        if oh % th:
            continue
        need = (3 * _tile_bytes(th + kh - 1, wp, c)  # 2 buffers + window
                + 4 * _tile_bytes(th, ow, c))  # 2 buffers + acc + tap
        if need <= _VMEM_BUDGET:
            return th
    return 1


def _fused_dw_kernel(x_ref, *rest, kh, kw, relu6):
    """One row tile: o[h,w,c] = act(Σ_{dh,dw} x[h+dh, w+dw, c]·k[dh·kw+dw, c] + b[c])."""
    halo_refs = rest[:kh - 1]
    k_ref, b_ref, o_ref, win_ref = rest[kh - 1:]
    th, ow = o_ref.shape[1], o_ref.shape[2]
    win_ref[0:th] = x_ref[0]
    for j, h_ref in enumerate(halo_refs):
        win_ref[th + j] = h_ref[0, 0]
    acc = None
    for dh in range(kh):
        for dw in range(kw):
            tap = win_ref[dh:dh + th, dw:dw + ow, :] * k_ref[dh * kw + dw, :]
            acc = tap if acc is None else acc + tap
    y = acc + b_ref[0, :]
    if relu6:
        y = jnp.clip(y, 0.0, 6.0)
    o_ref[0] = y.astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("kh", "kw", "relu6", "interpret"))
def fused_dw_call(xp, taps, bias, *, kh, kw, relu6=True, interpret=False):
    """xp [B, oh+kh−1, ow+kw−1, C] (pre-padded, f32) ⊛ taps [kh·kw, C] +
    bias [1, C] → [B, oh, ow, C]; stride 1."""
    bsz, hp, wp, c = xp.shape
    oh, ow = hp - kh + 1, wp - kw + 1
    th = row_tile(oh, wp, ow, c, kh)
    kernel = functools.partial(_fused_dw_kernel, kh=kh, kw=kw, relu6=relu6)
    # Halo row j of tile t is input row (t+1)·th + j — block index == row
    # index for a one-row block.
    halo_specs = [
        pl.BlockSpec((1, 1, wp, c),
                     lambda b, t, j=j: (b, (t + 1) * th + j, 0, 0),
                     memory_space=pltpu.VMEM)
        for j in range(kh - 1)
    ]
    return pl.pallas_call(
        kernel,
        grid_spec=pl.GridSpec(
            grid=(bsz, oh // th),
            in_specs=[
                pl.BlockSpec((1, th, wp, c), lambda b, t: (b, t, 0, 0),
                             memory_space=pltpu.VMEM),
                *halo_specs,
                pl.BlockSpec((kh * kw, c), lambda b, t: (0, 0),
                             memory_space=pltpu.VMEM),
                pl.BlockSpec((1, c), lambda b, t: (0, 0),
                             memory_space=pltpu.VMEM),
            ],
            out_specs=pl.BlockSpec((1, th, ow, c), lambda b, t: (b, t, 0, 0),
                                   memory_space=pltpu.VMEM),
            scratch_shapes=[pltpu.VMEM((th + kh - 1, wp, c), jnp.float32)],
        ),
        out_shape=jax.ShapeDtypeStruct((bsz, oh, ow, c), xp.dtype),
        interpret=interpret,
    )(xp, *([xp] * (kh - 1)), taps, bias)
