"""Pallas TPU kernel: fused I420 → RGB → dynamic resize → normalize.

The serving preprocess is the one hot op between the wire and the model
(SURVEY.md §1 L1 moved on-device). The XLA path (ops.image) is a chain of
unpack / upsample / convert / two einsums / normalize; this kernel fuses
the whole stage into a single VMEM-resident pass per image:

  - Y/U/V planes are read from the packed [3S/2, S] uint8 canvas,
  - chroma is upsampled and converted (BT.601) on the VPU,
  - the dynamic valid-region bilinear resize runs as two MXU matmuls with
    sampling matrices built on the fly from the per-image (h, w) scalars
    (delivered to the kernel through SMEM),
  - normalization ("inception" / "zero_one" / "raw") happens on the way out.

Output layout is planar [3, out_h, out_w] float32 per image (channel-last
3 would break the 128-lane tiling); the caller transposes, which XLA fuses
into the consumer.

Grid = (batch, row tiles). One whole 2048 canvas is 6 MB of uint8 before
the f32 planes built from it — 18 MB scoped against the 16 MB limit of a
v5e — so the canvas streams through VMEM in tiles of ``tk`` Y rows (plus
the ``tk/4`` packed U and V rows under them, as two more blocks of the
same array): the W-pass runs per tile, the H-pass accumulates
``A_h[:, tile] @ tile`` into three [out_h, out_w] scratch planes, and the
last tile converts and writes. ``tk`` (:func:`row_tile`) is the largest
multiple of 128 that divides S, up to 512 — u8 blocks tile at (32, 128),
so ``tk/4`` must be a multiple of 32. A canvas that is not a multiple of
128 (the 96/300-class sizes) goes in as ONE full block, which fits up to
1024; above that it is refused at config time (utils/config.py).

Use :func:`preprocess_i420` under ``jit``; ``interpret=True`` runs the same
kernel on CPU for tests. The engine enables it with ``resize="pallas"``
(yuv420 wire only); the XLA "matmul" path remains the portable default.

Interplay with the ragged wire (``cfg.ragged``): ragged packing ships
tight RGB pixels in a flat byte arena and reconstructs canvases on device
via :func:`ops.image.unpack_ragged` — it is an *upstream* stage that
replaces what arrives over the wire, not this kernel's resize. Ragged is
rgb-only today, and this kernel is yuv420-only, so the two are mutually
exclusive: the engine forces classic canvases when the wire is yuv420
(falling back with a warning if ``ragged`` was requested). Fusing a
ragged-arena gather into a pallas unpack+resize for the yuv wire is the
natural follow-up; the arena layout (byte offset + per-image (h, w) meta
rows) was chosen so that kernel could consume it unchanged.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# Color constants and the bilinear sampling-matrix construction are shared
# with the XLA paths (ops.image) — one source of truth for the parity the
# tests assert. All matrix builders are Mosaic-safe (2-D integer iota only).
from .image import (
    BT601_INV,
    _bilinear_matrix,
    _bilinear_matrix_chroma,
    _bilinear_matrix_chroma_packed,
)


# Largest Y-row tile. At 512 a 2048-wide tile is 1 MB of uint8 and 4 MB as
# f32, and the 2048 canvas compiles within a v5e's 16 MB scoped VMEM
# (tests/test_tpu_compile.py).
_ROW_TILE_MAX = 512
# Largest canvas that may go in as one full block (S not a multiple of 128).
WHOLE_CANVAS_MAX = 1024


def row_tile(s: int) -> int:
    """Y rows per grid step for canvas side ``s`` (module docstring)."""
    if s % 128:
        if s > WHOLE_CANVAS_MAX:
            raise ValueError(
                f"pallas preprocess: canvas {s} is not a multiple of 128 and "
                f"exceeds {WHOLE_CANVAS_MAX}, the largest that fits VMEM as "
                "one block")
        return s
    return max(t for t in range(128, _ROW_TILE_MAX + 1, 128) if s % t == 0)


def _kernel(hw_ref, *refs, s: int, tk: int, out_h: int, out_w: int, mode: str):
    # hw_ref holds the whole [B, 2] table in SMEM (a (1, 2) per-image block
    # trips Mosaic's block-tiling check at B > 1); index it by grid step.
    i = pl.program_id(0)
    k = pl.program_id(1)
    h = hw_ref[i, 0]
    w = hw_ref[i, 1]
    s2 = s // 2
    if tk == s:  # one full [3S/2, S] block: the planes are row ranges of it
        packed_ref, out_ref, aw_ref, awc_ref, yy_ref, uu_ref, vv_ref = refs
        y_ref = packed_ref.at[0, 0:s]
        u_ref = packed_ref.at[0, s : s + s // 4]
        v_ref = packed_ref.at[0, s + s // 4 :]
    else:
        y_blk, u_blk, v_blk, out_ref, aw_ref, awc_ref, yy_ref, uu_ref, vv_ref = refs
        y_ref, u_ref, v_ref = y_blk.at[0], u_blk.at[0], v_blk.at[0]

    # uint8 → int32 → float32: Mosaic rejects the direct u8→f32 cast when
    # the result feeds a matmul operand (fine on the elementwise path the
    # previous kernel used); the two-step cast lowers everywhere.
    as_f32 = lambda ref: ref[...].astype(jnp.int32).astype(jnp.float32)
    dot = functools.partial(jnp.dot, preferred_element_type=jnp.float32)

    @pl.when(k == 0)
    def _():
        # The W-pass matrices depend on the image alone: build them once.
        aw_ref[...] = _bilinear_matrix(out_w, w, s)  # (out_w, s)
        awc_ref[...] = _bilinear_matrix_chroma(out_w, w, s)  # (out_w, s/2)
        yy_ref[...] = jnp.zeros_like(yy_ref)
        uu_ref[...] = jnp.zeros_like(uu_ref)
        vv_ref[...] = jnp.zeros_like(vv_ref)

    # Plane-wise resize, conversion after (same order as the XLA matmul
    # path — resize and the BT.601 affine commute): chroma resizes at its
    # native half resolution through the folded sampling matrices instead
    # of being nearest-upsampled first — 4× less chroma MXU work, no repeat.
    # H-pass matrices restricted to this tile's rows.
    a_h = _bilinear_matrix(out_h, h, s, col0=k * tk, ncols=tk)  # (out_h, tk)
    a_he, a_ho = _bilinear_matrix_chroma_packed(
        out_h, h, s, col0=k * (tk // 4), ncols=tk // 4)  # (out_h, tk/4) ×2
    yy_ref[...] += dot(a_h, dot(as_f32(y_ref), aw_ref[...].T))

    # U/V stay in their packed (tk/4, s) canvas-width form — the lane
    # reshape to (tk/2, s/2) crashes Mosaic, so the H-pass deinterleaves on
    # the matrix side (see _bilinear_matrix_chroma_packed).
    def resize_chroma(rows_ref, acc_ref):
        rows = as_f32(rows_ref) - 128.0
        a_wc_t = awc_ref[...].T
        acc_ref[...] += dot(a_he, dot(rows[:, :s2], a_wc_t)) + dot(
            a_ho, dot(rows[:, s2:], a_wc_t))

    resize_chroma(u_ref, uu_ref)
    resize_chroma(v_ref, vv_ref)

    @pl.when(k == pl.num_programs(1) - 1)
    def _():
        yy, uu, vv = yy_ref[...], uu_ref[...], vv_ref[...]
        kr, kgu, kgv, kb = BT601_INV
        r = jnp.clip(yy + kr * vv, 0.0, 255.0)
        g = jnp.clip(yy + kgu * uu + kgv * vv, 0.0, 255.0)
        b = jnp.clip(yy + kb * uu, 0.0, 255.0)
        for c, x in enumerate((r, g, b)):
            if mode == "inception":
                x = x * (1.0 / 127.5) - 1.0
            elif mode == "zero_one":
                x = x * (1.0 / 255.0)
            out_ref[0, c, :, :] = x


@functools.partial(jax.jit, static_argnames=("out_h", "out_w", "mode", "interpret"))
def preprocess_i420(packed, hws, out_h: int, out_w: int, mode: str = "inception",
                    interpret: bool = False):
    """[B, 3S/2, S] uint8 I420 canvases + [B, 2] valid sizes →
    [B, out_h, out_w, 3] normalized float32."""
    batch, rows, s = packed.shape
    if rows != s * 3 // 2:
        raise ValueError(f"not an I420 canvas batch: {packed.shape}")
    if mode not in ("inception", "zero_one", "raw"):
        raise ValueError(f"unsupported normalize mode for pallas kernel: {mode}")
    tk = row_tile(s)
    kernel = functools.partial(
        _kernel, s=s, tk=tk, out_h=out_h, out_w=out_w, mode=mode)
    if tk == s:
        canvas_specs = [pl.BlockSpec((1, rows, s), lambda b, k: (b, 0, 0),
                                     memory_space=pltpu.VMEM)]
    else:
        # The U plane starts at row S and the V plane at row 5S/4: block
        # k of a (tk/4)-row blocking of the array is offset by 4S/tk
        # (resp. 5S/tk) blocks.
        canvas_specs = [
            pl.BlockSpec((1, tk, s), lambda b, k: (b, k, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, tk // 4, s), lambda b, k: (b, 4 * s // tk + k, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, tk // 4, s), lambda b, k: (b, 5 * s // tk + k, 0),
                         memory_space=pltpu.VMEM),
        ]
    planar = pl.pallas_call(
        kernel,
        grid_spec=pl.GridSpec(
            grid=(batch, s // tk),
            in_specs=[
                pl.BlockSpec((batch, 2), lambda b, k: (0, 0),
                             memory_space=pltpu.SMEM),
                *canvas_specs,
            ],
            out_specs=pl.BlockSpec(
                (1, 3, out_h, out_w), lambda b, k: (b, 0, 0, 0),
                memory_space=pltpu.VMEM
            ),
            scratch_shapes=[
                pltpu.VMEM((out_w, s), jnp.float32),
                pltpu.VMEM((out_w, s // 2), jnp.float32),
                pltpu.VMEM((out_h, out_w), jnp.float32),
                pltpu.VMEM((out_h, out_w), jnp.float32),
                pltpu.VMEM((out_h, out_w), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((batch, 3, out_h, out_w), jnp.float32),
        interpret=interpret,
    )(hws.astype(jnp.int32), *([packed] * len(canvas_specs)))
    return jnp.transpose(planar, (0, 2, 3, 1))
