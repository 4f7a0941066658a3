"""Image pipeline: host JPEG decode, on-device resize + normalize.

The reference does decode/resize/normalize on the host CPU with PIL before
``sess.run`` (SURVEY.md §1 L1). TPU-native redesign (BASELINE.json north
star: "image decode/resize/normalize moves on-device via jax.image"):

- the host does the one thing XLA cannot — entropy-coded JPEG/PNG decode —
  and pads the decoded uint8 image into a size-bucketed square canvas;
- the device does everything else inside the jitted serving function:
  bilinear resize *from the valid region* of the canvas (the source
  height/width arrive as runtime scalars — gather indices may be dynamic
  under jit as long as shapes are static, and canvas/output shapes are),
  then dtype conversion and normalization, fused by XLA into the model.

This keeps exactly one host→device transfer per batch (uint8 canvases, 4×
smaller than float32) and a handful of compiled executables (one per
(canvas bucket, batch bucket) pair) — no recompiles at request time.
"""

from __future__ import annotations

import io
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np


def decode_image(data: bytes) -> np.ndarray:
    """Decode JPEG/PNG/... bytes → RGB uint8 array (host CPU, PIL)."""
    from PIL import Image

    from ..native import count_pil_decode

    img = Image.open(io.BytesIO(data))
    img = img.convert("RGB")
    out = np.asarray(img, dtype=np.uint8)
    count_pil_decode(data)
    return out


def pick_bucket(size: int, buckets: tuple[int, ...]) -> int:
    for b in buckets:
        if size <= b:
            return b
    return buckets[-1]


def pad_to_canvas(img: np.ndarray, buckets: tuple[int, ...]) -> tuple[np.ndarray, tuple[int, int]]:
    """Pad (or downscale-then-pad) a decoded image into a square canvas.

    Returns (canvas uint8 [S, S, 3], (h, w) valid region). Images larger than
    the biggest bucket are host-downscaled first — at >2048px the decode
    already dominates, and shipping 4k canvases would waste HBM bandwidth.
    """
    h, w = img.shape[:2]
    s = pick_bucket(max(h, w), buckets)
    if max(h, w) > s:
        from PIL import Image

        scale = s / max(h, w)
        nh, nw = max(1, int(h * scale)), max(1, int(w * scale))
        img = np.asarray(Image.fromarray(img).resize((nw, nh), Image.BILINEAR), dtype=np.uint8)
        h, w = nh, nw
    canvas = np.zeros((s, s, 3), np.uint8)
    canvas[:h, :w] = img
    return canvas, (h, w)


def fit_to_bucket(
    img: np.ndarray, buckets: tuple[int, ...]
) -> tuple[np.ndarray, tuple[int, int], int]:
    """Tight sibling of :func:`pad_to_canvas` for the ragged wire: pick
    the canvas bucket and host-downscale an oversized image to fit it,
    but do NOT pad — the ragged arena ships native-stride bytes. Returns
    (tight uint8 [h, w, 3], (h, w), canvas bucket side)."""
    h, w = img.shape[:2]
    s = pick_bucket(max(h, w), buckets)
    if max(h, w) > s:
        from PIL import Image

        scale = s / max(h, w)
        nh, nw = max(1, int(h * scale)), max(1, int(w * scale))
        img = np.asarray(Image.fromarray(img).resize((nw, nh), Image.BILINEAR), dtype=np.uint8)
        h, w = nh, nw
    return np.ascontiguousarray(img, dtype=np.uint8), (h, w), s


# --------------------------------------------------------------------------
# ragged packed wire (ROADMAP item 5)
# --------------------------------------------------------------------------
#
# Classic batches ship one [S, S, 3] canvas per image — for ~200 px uploads
# on the 256 canvas that is ~70% padding bytes over the host→device link
# (measured, PR 11). The ragged wire ships a FLAT byte arena instead: each
# image's tight native-stride rows (w*3 bytes per row, no canvas padding)
# bump-allocated end to end, images freely spanning arena-row boundaries,
# plus one int32[K, 4] meta table of (byte_offset, h, w, valid). The device
# scatters each image back to its canvas slot below; the existing dynamic
# valid-region resize then consumes the canvases unchanged, which is what
# keeps golden parity exact — same bytes, same placement, same taps.

# Part of the AOT executable-cache key for unpack executables
# (serving/aotcache.py): bump when the unpack computation below changes
# (arena layout, meta schema, hole convention), so on-disk executables
# serialized against the old program can never load for the new one.
RAGGED_UNPACK_VERSION = 3


def unpack_kernel_applies(s: int, n_devices: int) -> bool:
    """Whether a replica should ship its arenas as uint32 words, which
    :func:`unpack_ragged` hands to the Mosaic kernel: on a TPU, on a mesh
    of one device (GSPMD cannot partition the kernel over a sharded
    arena), at a canvas whose rows are whole 512-byte lane rows (512 and
    its multiples). Everything else ships bytes and takes the XLA
    formulation: the default 256 canvas, the CPU, sharded replicas."""
    from .pallas_unpack import kernel_fits

    return (jax.default_backend() == "tpu" and n_devices == 1
            and kernel_fits(s))


def unpack_ragged(arena, meta, s: int, interpret: bool = False):
    """Flat ragged byte arena + per-image meta → host-identical canvases.

    ``arena``: the packed tight-row bytes — image ``i``'s pixels occupy
    ``meta[i, 0] + (y*w + x)*3 + c`` — as uint8 of any shape (flattened
    here), or as flat uint32: the same bytes viewed as little-endian words
    (``buf.view(np.uint32)`` on the host), a whole number of canvases
    long. The dtype picks the implementation: words go to the Mosaic
    row-block kernel (ops/pallas_unpack.py; ``interpret`` runs it through
    the Pallas interpreter, for tests off the chip), bytes to the XLA
    gather below, which is also the reference the kernel is tested
    against. Bytes cannot become words on the device: the re-view pads
    its minor dimension of 4 to 128 lanes.
    ``meta``: int32 [K, 4] rows ``(byte_offset, h, w, valid)``; ``valid=0``
    marks a hole (zero canvas, hw pinned to the 1×1 hole convention the
    classic slab path uses).

    Returns ``(canvases uint8 [K, s, s, 3], hws int32 [K, 2])`` —
    bit-identical to the classic host pad-to-canvas path for the same
    decoded pixels: exact placement, no resample. Window starts are
    dynamic but shapes are static, so one jitted instance serves every
    batch of the same (s, K, arena length).

    The gather moves one canvas ROW per index — a contiguous ``3s``-byte
    window starting at the image row's first byte, masked past ``3w`` —
    not one byte per index: a per-byte index tensor tiles to 128× its size
    on a TPU (4 GB of temporaries at canvas 512 × batch 32, and past the
    16 GB of a v5e from canvas 1024 on; found compiling for the chip).
    On a TPU it lowers to a serial loop of one slice and one update per
    canvas row (5.7 us a row on a v5e), which is what the kernel replaces.
    """
    # named_scope: the ops' metadata carries the phase; the module's name
    # (the caller's jit) is untouched.
    with jax.named_scope("unpack"):
        arena = jnp.asarray(arena)  # eager numpy callers trace too
        meta = jnp.asarray(meta)
        ok = meta[:, 3] > 0
        hws = jnp.where(ok[:, None], meta[:, 1:3], jnp.ones((1, 2), jnp.int32))
        hws = hws.astype(jnp.int32)
        if arena.dtype == jnp.uint32:
            from .pallas_unpack import unpack_planes

            planes = unpack_planes(arena.reshape(-1), meta, s=s,
                                   interpret=interpret)
            # [K, 3, s, s] → [K, s, s, 3]: on a TPU the canvases' device
            # layout is planar already, and this is a re-view, not a copy.
            return jnp.transpose(planes, (0, 2, 3, 1)), hws
        flat = arena.reshape(-1)
        row = 3 * s
        # A window that would run past the arena's end is clamped back by
        # dynamic_slice, which would shift the last image's rows: give every
        # valid row's window room to end inside the buffer.
        flat = jnp.concatenate([flat, jnp.zeros((row,), jnp.uint8)])
        y = jax.lax.broadcasted_iota(jnp.int32, (s, 1), 0)
        xb = jax.lax.broadcasted_iota(jnp.int32, (1, row), 1)

        def one(m):
            off, h, w, valid = m[0], m[1], m[2], m[3]
            starts = off + y[:, 0] * (w * 3)
            rows = jax.vmap(
                lambda st: jax.lax.dynamic_slice(flat, (st,), (row,)))(starts)
            mask = (valid > 0) & (y < h) & (xb < w * 3)
            return jnp.where(mask, rows, jnp.uint8(0)).reshape(s, s, 3)

        return jax.vmap(one)(meta), hws


# --------------------------------------------------------------------------
# YUV 4:2:0 wire format
# --------------------------------------------------------------------------
#
# The host→device hop carries decoded pixels over the host's PCIe; how far
# wire bytes bound e2e throughput there is not measured on a directly
# attached chip (ROADMAP D2). JPEG stores YCbCr 4:2:0 natively, so shipping
# I420 planes (1.5 B/px) instead of RGB (3 B/px) halves the transfer, and
# the colorspace conversion runs on-device. Layout: one packed uint8 array [3S/2, S] per image — Y plane rows
# [0, S), then U and V at quarter resolution reshaped to S/4 rows each
# (classic I420 frame). S must be a multiple of 4.


# Full-range BT.601 (JPEG/JFIF). Forward (RGB→YCbCr) and inverse share
# these definitions with the pallas kernel — one source of truth for the
# parity the tests assert.
BT601_FWD = (
    (0.299, 0.587, 0.114),
    (-0.168736, -0.331264, 0.5),
    (0.5, -0.418688, -0.081312),
)
BT601_INV = (1.402, -0.344136, -0.714136, 1.772)  # (kr_v, kg_u, kg_v, kb_u)


def patch_tokens(canvases, hws, patch: int):
    """Canvases to token sequences of real pixels: no resize.

    ``canvases`` uint8 [B, S, S, 3] (an image top-left in its canvas),
    ``hws`` int32 [B, 2]. Every whole ``patch`` x ``patch`` block of an
    image's own pixels is one token, in raster order of the image's own
    grid, packed to the front of the row's ``(S / patch) ** 2`` token slots:
    the canvas bucket is the length bucket. Returns (tokens float32 [B,
    slots, patch * patch * 3] as ``pixel / 127.5 - 1``, zero in the padding
    slots; lengths int32 [B])."""
    b, s = canvases.shape[0], canvases.shape[1]
    g = s // patch
    blocks = canvases[:, : g * patch, : g * patch].reshape(b, g, patch, g, patch, 3)
    blocks = blocks.transpose(0, 1, 3, 2, 4, 5).reshape(b, g * g, patch * patch * 3)
    ph, pw = hws[:, 0] // patch, hws[:, 1] // patch
    lengths = (ph * pw).astype(jnp.int32)
    slot = jnp.arange(g * g, dtype=jnp.int32)[None, :]
    cols = jnp.maximum(pw, 1)[:, None]
    src = (slot // cols) * g + slot % cols                      # slot t is the image's block (t // pw, t % pw)
    real = slot < lengths[:, None]
    blocks = jnp.take_along_axis(blocks, jnp.where(real, src, 0)[:, :, None], axis=1)
    tokens = blocks.astype(jnp.float32) / 127.5 - 1.0
    return jnp.where(real[:, :, None], tokens, 0.0), lengths


def rgb_to_yuv420_canvas(canvas: np.ndarray) -> np.ndarray:
    """Host-side reference packer: RGB uint8 [S, S, 3] → I420 uint8 [3S/2, S].

    Full-range BT.601 (the JPEG/JFIF convention, matching libjpeg output);
    chroma is 2×2 box-subsampled. The native extension supersedes this on
    the hot path by decoding JPEGs straight to I420.
    """
    s = canvas.shape[0]
    if s % 4:
        raise ValueError(f"yuv420 canvas size must be a multiple of 4, got {s}")
    rgb = canvas.astype(np.float32)
    r, g, b = rgb[..., 0], rgb[..., 1], rgb[..., 2]
    (yr, yg, yb), (ur, ug, ub), (vr, vg, vb) = BT601_FWD
    y = yr * r + yg * g + yb * b
    u = ur * r + ug * g + ub * b + 128.0
    v = vr * r + vg * g + vb * b + 128.0
    u = u.reshape(s // 2, 2, s // 2, 2).mean(axis=(1, 3))
    v = v.reshape(s // 2, 2, s // 2, 2).mean(axis=(1, 3))
    packed = np.empty((s * 3 // 2, s), np.uint8)
    packed[:s] = np.clip(y + 0.5, 0, 255).astype(np.uint8)
    packed[s : s + s // 4] = np.clip(u + 0.5, 0, 255).astype(np.uint8).reshape(s // 4, s)
    packed[s + s // 4 :] = np.clip(v + 0.5, 0, 255).astype(np.uint8).reshape(s // 4, s)
    return packed


def yuv420_to_rgb(packed, s: int):
    """Device-side unpack: I420 uint8 [3S/2, S] → RGB float32 [S, S, 3].

    Nearest-neighbor chroma upsample (chroma is already lossy at 4:2:0;
    XLA fuses the whole conversion into the consumer).
    """
    y = packed[:s].astype(jnp.float32)
    u = packed[s : s + s // 4].reshape(s // 2, s // 2).astype(jnp.float32) - 128.0
    v = packed[s + s // 4 :].reshape(s // 2, s // 2).astype(jnp.float32) - 128.0
    u = jnp.repeat(jnp.repeat(u, 2, axis=0), 2, axis=1)
    v = jnp.repeat(jnp.repeat(v, 2, axis=0), 2, axis=1)
    kr, kgu, kgv, kb = BT601_INV
    r = y + kr * v
    g = y + kgu * u + kgv * v
    b = y + kb * u
    return jnp.clip(jnp.stack([r, g, b], axis=-1), 0.0, 255.0)


# --------------------------------------------------------------------------
# device side
# --------------------------------------------------------------------------


def _dynamic_axis_coords(out_size: int, in_size, total: int):
    """Bilinear sample coordinates for a dynamic valid extent ``in_size``
    inside a static canvas axis of length ``total`` (half-pixel centers).

    Returns float32 ``(lo, hi, frac)``, each shaped (out_size, 1) — 2-D
    because this is the single source of truth for all three resize
    implementations, including the pallas kernel, and Mosaic requires ≥2-D
    *integer* iota (cast to float after). ``lo``/``hi`` are exact integers
    stored as float.
    """
    i = jax.lax.broadcasted_iota(jnp.int32, (out_size, 1), 0).astype(jnp.float32)
    in_f = in_size.astype(jnp.float32)
    c = (i + 0.5) * (in_f / out_size) - 0.5
    c = jnp.clip(c, 0.0, in_f - 1.0)
    lo = jnp.floor(c)
    hi = jnp.minimum(jnp.minimum(lo + 1.0, in_f - 1.0), float(total - 1))
    return lo, hi, c - lo


def resize_from_valid(canvas, hw, out_h: int, out_w: int):
    """Bilinear-resize the valid ``hw``-sized top-left region of ``canvas``
    to (out_h, out_w). Shapes are static; ``hw`` is data.

    canvas: float32/uint8 [S, S, 3]; hw: int32 [2].
    """
    s = canvas.shape[0]
    x = canvas.astype(jnp.float32)
    h_lo, h_hi, h_w = (a[:, 0] for a in _dynamic_axis_coords(out_h, hw[0], s))
    w_lo, w_hi, w_w = (a[:, 0] for a in _dynamic_axis_coords(out_w, hw[1], s))
    h_lo, h_hi = h_lo.astype(jnp.int32), h_hi.astype(jnp.int32)
    w_lo, w_hi = w_lo.astype(jnp.int32), w_hi.astype(jnp.int32)
    top = x[h_lo, :, :] * (1 - h_w)[:, None, None] + x[h_hi, :, :] * h_w[:, None, None]
    out = top[:, w_lo, :] * (1 - w_w)[None, :, None] + top[:, w_hi, :] * w_w[None, :, None]
    return out


def _bilinear_matrix(out_size: int, in_size, total: int, col0=0,
                     ncols: int | None = None):
    """Dense (out_size, total) bilinear sampling matrix for a dynamic valid
    extent ``in_size`` inside a static axis of length ``total``.

    Each row holds the two bilinear taps for one output coordinate, so
    ``A @ x`` IS the resize along that axis. On TPU this turns the dynamic
    gather into two MXU matmuls (gathers run on the scalar/vector units and
    serialize; matmuls are what the hardware is built for). Rows sum to 1.

    ``col0``/``ncols`` return only columns [col0, col0 + ncols) — the
    block a row-tiled consumer (the pallas kernel) multiplies one tile by.
    """
    lo, hi, frac = _dynamic_axis_coords(out_size, in_size, total)  # (out, 1)
    cols = (jax.lax.broadcasted_iota(
        jnp.int32, (out_size, total if ncols is None else ncols), 1
    ) + col0).astype(jnp.float32)
    a = jnp.where(cols == lo, 1.0 - frac, 0.0)
    # hi == lo at the clamp edge: add, don't overwrite, so weights sum to 1.
    return a + jnp.where(cols == hi, frac, 0.0)


def resize_from_valid_mm(canvas, hw, out_h: int, out_w: int):
    """MXU-friendly variant of :func:`resize_from_valid`: separable bilinear
    resize as ``A_h @ canvas @ A_w^T`` (einsum → batched matmul on the MXU).

    Numerically identical to the gather version (same coordinates, same
    taps, float32 throughout).
    """
    a_h = _bilinear_matrix(out_h, hw[0], canvas.shape[0])
    a_w = _bilinear_matrix(out_w, hw[1], canvas.shape[1])
    x = canvas.astype(jnp.float32)
    t = jnp.einsum("os,swc->owc", a_h, x)
    return jnp.einsum("owc,vw->ovc", t, a_w)


RESIZERS = {"gather": resize_from_valid, "matmul": resize_from_valid_mm}


# --------------------------------------------------------------------------
# plane-wise YUV resize (the yuv420 matmul fast path)
# --------------------------------------------------------------------------
#
# Resize and colorspace conversion are both linear, so they commute: resizing
# the Y/U/V PLANES and converting at output resolution equals converting at
# canvas resolution and resizing RGB (up to f32 reassociation). Clipping does
# NOT commute on out-of-gamut YUV — JPEG-decoded chroma produces such values
# routinely — so this path (clip after resize) diverges from the old
# convert-clip-resize order there, bounded by the chroma excursion and tested
# in tests/test_stem.py::test_plane_resize_matches_rgb_path. The plane form
# is strictly better shaped for the TPU:
#   - matmuls run on 2-D planes (lanes = image width) instead of
#     channels-minor [S, S, 3] tensors (3 of 128 lanes);
#   - chroma is resized at its native half resolution — the nearest-neighbor
#     upsample folds into the sampling matrix (A·R, exact) for 4× less
#     chroma matmul work and no materialized upsampled planes;
#   - the [S, S, 3] float RGB intermediate never exists.
# Profiled on v5e (serve program, batch 32): the RGB-path preprocess +
# the stem's s2d fold cost ~1.1 ms/batch; this path removes most of it.


def _fold_chroma(a):
    """(out, S) sampling matrix → (out, S/2) acting on the half-res plane:
    A_c = A @ R with R the ×2 nearest-neighbor upsample — exact fold."""
    o, s = a.shape
    return a.reshape(o, s // 2, 2).sum(axis=2)


def _bilinear_matrix_chroma(out_size: int, in_size, total: int):
    """The chroma fold built directly from the sampling coordinates:
    identical floats to ``_fold_chroma(_bilinear_matrix(...))`` (each tap's
    column index just maps px → px//2), but Mosaic-safe — no 3-D reshape
    or lane-strided slice, same 2-D iota pattern as ``_bilinear_matrix``."""
    lo, hi, frac = _dynamic_axis_coords(out_size, in_size, total)
    cols = jax.lax.broadcasted_iota(jnp.int32, (out_size, total // 2), 1).astype(
        jnp.float32
    )
    a = jnp.where(cols == jnp.floor(lo / 2), 1.0 - frac, 0.0)
    return a + jnp.where(cols == jnp.floor(hi / 2), frac, 0.0)


def _bilinear_matrix_chroma_packed(out_size: int, in_size, total: int,
                                   col0=0, ncols: int | None = None):
    """Chroma H-pass matrices acting on the PACKED I420 chroma rows.

    The wire stores a (S/2, S/2) chroma plane as (S/4, S) canvas-width rows
    — packed row k holds plane rows 2k (lanes [0, S/2)) and 2k+1 (lanes
    [S/2, S)). Mosaic cannot lower the (S/4, S) → (S/2, S/2) lane reshape
    (crashes the TPU compiler — found by bisection 2026-07-30), so the
    pallas kernel deinterleaves on the MATRIX side instead: returns
    ``(even, odd)`` of shape (out, S/4) with
    ``A_c @ plane == even @ rows[:, :S/2] + odd @ rows[:, S/2:]``
    exactly (same two taps per row, zeros elsewhere). ``col0``/``ncols``
    return only the columns of packed rows [col0, col0 + ncols)."""
    lo, hi, frac = _dynamic_axis_coords(out_size, in_size, total)
    rl, rh = jnp.floor(lo / 2), jnp.floor(hi / 2)
    cols4 = (jax.lax.broadcasted_iota(
        jnp.int32, (out_size, total // 4 if ncols is None else ncols), 1
    ) + col0).astype(jnp.float32)
    even = jnp.where(2 * cols4 == rl, 1.0 - frac, 0.0) + jnp.where(
        2 * cols4 == rh, frac, 0.0
    )
    odd = jnp.where(2 * cols4 + 1 == rl, 1.0 - frac, 0.0) + jnp.where(
        2 * cols4 + 1 == rh, frac, 0.0
    )
    return even, odd


def _split_planes(packed):
    """I420 [3S/2, S] uint8 → (y [S,S], u, v [S/2,S/2]) float32, chroma
    centered at 0 (the -128 offset folded in here)."""
    s = packed.shape[-1]
    y = packed[:s].astype(jnp.float32)
    u = packed[s : s + s // 4].reshape(s // 2, s // 2).astype(jnp.float32) - 128.0
    v = packed[s + s // 4 :].reshape(s // 2, s // 2).astype(jnp.float32) - 128.0
    return y, u, v


def _combine_rgb(y, u, v):
    kr, kgu, kgv, kb = BT601_INV
    r = y + kr * v
    g = y + kgu * u + kgv * v
    b = y + kb * u
    return jnp.clip(jnp.stack([r, g, b], axis=-1), 0.0, 255.0)


def resize_yuv_planes(packed, hw, out_h: int, out_w: int):
    """I420 canvas [3S/2, S] + valid hw → RGB float32 [out_h, out_w, 3].

    Same sampling coordinates and taps as ``yuv420_to_rgb`` +
    ``resize_from_valid_mm`` (the matrices are shared code); only the
    association order differs.
    """
    y, u, v = _split_planes(packed)
    s = y.shape[0]
    a_h = _bilinear_matrix(out_h, hw[0], s)
    a_w = _bilinear_matrix(out_w, hw[1], s)
    a_hc, a_wc = _fold_chroma(a_h), _fold_chroma(a_w)
    rs = lambda a, p, b: a @ p @ b.T
    return _combine_rgb(rs(a_h, y, a_w), rs(a_hc, u, a_wc), rs(a_hc, v, a_wc))


def _s2d_pair(a, out: int):
    """Sampling matrix (out, S) → (⌈out/2⌉, 2, S): rows regrouped into
    (cell, phase), zero row appended for odd ``out`` (the conv-side kernel
    has zero taps there — ops/stem.py)."""
    cells = (out + 1) // 2
    return jnp.pad(a, ((0, 2 * cells - out), (0, 0))).reshape(cells, 2, a.shape[1])


def resize_yuv_planes_s2d(packed, hw, out_h: int, out_w: int, mode: str):
    """Plane resize emitting the space-to-depth layout directly:
    [3S/2, S] → [⌈out_h/2⌉, ⌈out_w/2⌉, 12], channels (p, q, rgb) with rgb
    fastest — exactly ``pack_s2d(resize_yuv_planes(...))`` but the fold is
    free: the einsums write cells directly, no materialized transpose.
    Normalization (``mode``) is applied before the channel merge so
    channel-reordering normalizers (caffe BGR) act on the rgb triple.
    """
    y, u, v = _split_planes(packed)
    s = y.shape[0]
    ah = _s2d_pair(_bilinear_matrix(out_h, hw[0], s), out_h)
    aw = _s2d_pair(_bilinear_matrix(out_w, hw[1], s), out_w)
    ahc = _fold_chroma(ah.reshape(-1, s)).reshape(ah.shape[0], 2, s // 2)
    awc = _fold_chroma(aw.reshape(-1, s)).reshape(aw.shape[0], 2, s // 2)

    def rs(a3, p, b3):
        t = jnp.einsum("hps,sw->hpw", a3, p)
        return jnp.einsum("hpv,wqv->hwpq", t, b3)

    rgb = _combine_rgb(rs(ah, y, aw), rs(ahc, u, awc), rs(ahc, v, awc))
    rgb = NORMALIZERS[mode](rgb)  # [ch, cw, 2, 2, 3]
    ch, cw = rgb.shape[0], rgb.shape[1]
    # Odd extents: the phase-1 pad lane must hold literal zeros (the
    # pack_s2d convention; the stem's kernel taps there are zero anyway),
    # not normalized-zero — offset normalizers would otherwise leak into
    # it. Static mask multiplies fuse into the epilogue (a .at[].set would
    # lower to a scatter — profiled at ~0.13 ms/batch on v5e).
    if out_h % 2:
        mask = jnp.ones((ch, 1, 2, 1, 1), jnp.float32).at[-1, :, 1].set(0.0)
        rgb = rgb * mask
    if out_w % 2:
        mask = jnp.ones((1, cw, 1, 2, 1), jnp.float32).at[:, -1, :, 1].set(0.0)
        rgb = rgb * mask
    return rgb.reshape(ch, cw, 12)


NORMALIZERS = {
    "inception": lambda x: x / 127.5 - 1.0,  # [-1, 1]; Inception/MobileNet family
    "zero_one": lambda x: x / 255.0,
    # Caffe-style ResNet-50: RGB→BGR + per-channel mean subtraction.
    "caffe": lambda x: x[..., ::-1] - jnp.array([103.939, 116.779, 123.68], jnp.float32),
    "raw": lambda x: x,
}


@partial(jax.jit, static_argnums=(2, 3, 4))
def preprocess_batch(canvases, hws, out_h: int, out_w: int, mode: str):
    """[B, S, S, 3] uint8 canvases + [B, 2] valid sizes → [B, out_h, out_w, 3]
    normalized float32, entirely on-device."""
    resize = jax.vmap(lambda c, hw: resize_from_valid(c, hw, out_h, out_w))
    return NORMALIZERS[mode](resize(canvases, hws))


def make_preprocess_fn(
    out_h: int,
    out_w: int,
    mode: str,
    wire: str = "rgb",
    resize: str = "matmul",
    s2d: bool = False,
):
    """Un-jitted preprocess for fusing into a larger jitted serving fn.

    ``wire`` selects the host→device canvas encoding: "rgb" takes uint8
    [B, S, S, 3]; "yuv420" takes packed I420 uint8 [B, 3S/2, S] and converts
    on-device. ``resize`` picks the implementation: "matmul" (separable
    bilinear as MXU matmuls — the TPU-native default; on the yuv420 wire it
    runs plane-wise with the conversion after, see ``resize_yuv_planes``)
    or "gather" (dynamic-index taps; better on CPU/debug).

    ``s2d=True`` emits the stem handshake layout [B, ⌈out_h/2⌉, ⌈out_w/2⌉,
    12] (``ops.stem.pack_s2d`` order) for models built with
    ``input_format="s2d"`` — the yuv420 matmul path writes it directly from
    the resize einsums; other paths fold the standard output.
    """
    if wire not in ("rgb", "yuv420"):
        raise ValueError(f"unknown wire format {wire!r}")

    if wire == "yuv420" and resize == "matmul":
        if s2d:
            return jax.vmap(
                lambda p, hw: resize_yuv_planes_s2d(p, hw, out_h, out_w, mode)
            )
        return jax.vmap(
            lambda p, hw: NORMALIZERS[mode](resize_yuv_planes(p, hw, out_h, out_w))
        )

    resize_one = RESIZERS[resize]

    def fn(canvases, hws):
        if wire == "yuv420":
            s = canvases.shape[-1]
            canvases = jax.vmap(lambda p: yuv420_to_rgb(p, s))(canvases)
        resized = jax.vmap(lambda c, hw: resize_one(c, hw, out_h, out_w))(canvases, hws)
        out = NORMALIZERS[mode](resized)
        if s2d:
            from .stem import pack_s2d

            out = pack_s2d(out)
        return out

    return fn
