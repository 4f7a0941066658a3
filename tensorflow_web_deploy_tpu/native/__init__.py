"""Native host-staging extension: libjpeg → serving canvas, via ctypes.

The runtime around the XLA compute path keeps its one non-XLA compute
stage — entropy-coded JPEG decode — in C (``decode.c``), decoded straight
into the engine's wire formats (RGB canvas or packed I420) with DCT-domain
downscaling for oversized uploads. ctypes releases the GIL during the call,
so the server's request threads decode in parallel.

``decode_to_canvas()`` is the public entry; it falls back to the PIL path
(:mod:`..ops.image`) whenever the extension is unavailable (no compiler,
no libjpeg) or the input isn't a JPEG the C path supports (PNG, CMYK, …).
The extension is built on first use with the system compiler and cached
under ``.native_cache/``; ``python -m tensorflow_web_deploy_tpu.native.build``
prebuilds it explicitly.
"""

from __future__ import annotations

import ctypes
import hashlib
import logging
import os
import subprocess
from pathlib import Path

import numpy as np

from ..utils.locks import named_lock

log = logging.getLogger("tpu_serve.native")

_SRC = Path(__file__).resolve().parent / "decode.c"
_CACHE_DIR = Path(
    os.environ.get(
        "TPU_SERVE_NATIVE_CACHE",
        str(Path(__file__).resolve().parent.parent.parent / ".native_cache"),
    )
)

_lock = named_lock("native.build_lock")
_lib: ctypes.CDLL | None = None
_lib_tried = False

# Which decoder really served: process-wide counts behind the /stats
# "decode" block. A missing compiler or libjpeg turns every JPEG over to
# PIL with one log line; ``pil_jpeg_decodes_total`` is where that shows.
_count_lock = named_lock("native.counters_lock")
_decodes = {"native_decodes_total": 0, "pil_decodes_total": 0,
            "pil_jpeg_decodes_total": 0}


def count_pil_decode(data: bytes) -> None:
    """One successful PIL decode (ops.image.decode_image calls this)."""
    with _count_lock:
        _decodes["pil_decodes_total"] += 1
        if data[:2] == b"\xff\xd8":
            _decodes["pil_jpeg_decodes_total"] += 1


def _count_native_decode() -> None:
    with _count_lock:
        _decodes["native_decodes_total"] += 1


def stats() -> dict:
    """The /stats "decode" block: is the extension loaded, and how many
    images each decoder has served in this process."""
    with _count_lock:
        out = dict(_decodes)
    out["native"] = _lib is not None
    return out


def _build(src: Path, out: Path) -> None:
    """Compile to a temp path and atomically rename into place, so
    concurrent builders never load a half-written .so and a killed compile
    can't poison the cache."""
    out.parent.mkdir(parents=True, exist_ok=True)
    cc = os.environ.get("CC", "cc")
    tmp = out.with_suffix(f".tmp{os.getpid()}.so")
    cmd = [cc, "-O3", "-shared", "-fPIC", "-o", str(tmp), str(src), "-ljpeg"]
    try:
        subprocess.run(cmd, check=True, capture_output=True, timeout=120)
        os.replace(tmp, out)
    finally:
        tmp.unlink(missing_ok=True)


def _load() -> ctypes.CDLL | None:
    """Build (if needed) and load the extension; None if impossible."""
    global _lib, _lib_tried
    if _lib is not None or _lib_tried:
        return _lib
    with _lock:
        if _lib is not None or _lib_tried:
            return _lib
        _lib_tried = True
        if os.environ.get("TPU_SERVE_NO_NATIVE"):
            return None
        try:
            tag = hashlib.sha256(_SRC.read_bytes()).hexdigest()[:16]
            so = _CACHE_DIR / f"libtwd_decode_{tag}.so"
            if not so.exists():
                # twdlint: disable=no-blocking-under-lock(one-time lazy compile; the double-checked lock deliberately serializes concurrent builders so only one cc runs and nobody loads a half-written .so — steady-state callers hit the cached handle and never reach this)
                _build(_SRC, so)
            lib = ctypes.CDLL(str(so))
            lib.twd_jpeg_dims.restype = ctypes.c_int
            lib.twd_jpeg_dims.argtypes = [
                ctypes.c_char_p,
                ctypes.c_size_t,
                ctypes.POINTER(ctypes.c_int),
                ctypes.POINTER(ctypes.c_int),
            ]
            lib.twd_decode_jpeg.restype = ctypes.c_int
            lib.twd_decode_jpeg.argtypes = [
                ctypes.c_char_p,
                ctypes.c_size_t,
                ctypes.POINTER(ctypes.c_ubyte),
                ctypes.c_int,
                ctypes.c_int,
                ctypes.POINTER(ctypes.c_int),
                ctypes.POINTER(ctypes.c_int),
            ]
            lib.twd_decode_jpeg_slot.restype = ctypes.c_int
            lib.twd_decode_jpeg_slot.argtypes = [
                ctypes.c_char_p,
                ctypes.c_size_t,
                ctypes.POINTER(ctypes.c_ubyte),
                ctypes.c_size_t,
                ctypes.c_int,
                ctypes.c_int,
                ctypes.c_int,
                ctypes.POINTER(ctypes.c_int),
                ctypes.POINTER(ctypes.c_int),
            ]
            lib.twd_decode_jpeg_packed.restype = ctypes.c_int
            lib.twd_decode_jpeg_packed.argtypes = [
                ctypes.c_char_p,
                ctypes.c_size_t,
                ctypes.POINTER(ctypes.c_ubyte),
                ctypes.c_size_t,
                ctypes.c_int,
                ctypes.POINTER(ctypes.c_int),
                ctypes.POINTER(ctypes.c_int),
            ]
            _lib = lib
            log.info("native decode extension loaded (%s)", so.name)
        except Exception as e:  # missing compiler/libjpeg: PIL path serves fine
            log.warning("native decode extension unavailable (%s); using PIL", e)
            _lib = None
    return _lib


def available() -> bool:
    return _load() is not None


def jpeg_dims(data: bytes) -> tuple[int, int] | None:
    """(height, width) from the JPEG header, or None if not decodable here."""
    lib = _load()
    if lib is None or len(data) < 3 or data[:2] != b"\xff\xd8":
        return None
    h = ctypes.c_int()
    w = ctypes.c_int()
    if lib.twd_jpeg_dims(data, len(data), ctypes.byref(h), ctypes.byref(w)) != 0:
        return None
    return h.value, w.value


def plan_decode(
    data: bytes, buckets: tuple[int, ...], wire: str
) -> tuple[int, tuple[int, ...], tuple[int, int]] | None:
    """Staging plan for a JPEG the native path can decode: probe the header
    and return ``(canvas_bucket, row_shape, original (h, w))`` — everything
    a caller needs to lease a slab slot of the right shape BEFORE decoding,
    so :func:`decode_into_row` can land the pixels straight in the slot.
    None means the bytes must take the PIL path."""
    lib = _load()
    if lib is None or len(data) < 3 or data[:2] != b"\xff\xd8":
        return None
    dims = jpeg_dims(data)
    if dims is None:
        return None
    # Bucket by the *decoded* size: the C side DCT-downscales by up to 1/8,
    # so anything over 8x the largest bucket falls back to PIL.
    from ..ops.image import pick_bucket

    h0, w0 = dims
    m = max(h0, w0)
    top = buckets[-1]
    if m > 8 * top:
        return None
    denom = 1
    while denom <= 8 and (m + denom - 1) // denom > top:
        denom *= 2
    s = pick_bucket((m + denom - 1) // denom, buckets)
    shape = (s * 3 // 2, s) if wire == "yuv420" else (s, s, 3)
    return s, shape, (h0, w0)


def plan_decode_packed(
    data: bytes, buckets: tuple[int, ...]
) -> tuple[int, int, tuple[int, int], tuple[int, int]] | None:
    """Ragged-wire staging plan: probe the JPEG header and return
    ``(canvas_bucket, need_bytes, decoded (h, w), original (h, w))`` — the
    exact byte span a ragged lease must reserve before
    :func:`decode_packed_into` lands tight rows in it. The decoded extent
    is deterministic from the header: libjpeg's DCT downscale emits
    ``ceil(dim / denom)`` for the chosen power-of-two denominator, the same
    arithmetic :func:`plan_decode` uses for bucket choice. None means the
    bytes must take the PIL path (non-JPEG, >8x the top bucket, ...)."""
    lib = _load()
    if lib is None or len(data) < 3 or data[:2] != b"\xff\xd8":
        return None
    dims = jpeg_dims(data)
    if dims is None:
        return None
    from ..ops.image import pick_bucket

    h0, w0 = dims
    m = max(h0, w0)
    top = buckets[-1]
    if m > 8 * top:
        return None
    denom = 1
    while denom <= 8 and (m + denom - 1) // denom > top:
        denom *= 2
    dh = (h0 + denom - 1) // denom
    dw = (w0 + denom - 1) // denom
    s = pick_bucket(max(dh, dw), buckets)
    return s, dh * dw * 3, (dh, dw), (h0, w0)


def decode_packed_into(
    data: bytes, dst: np.ndarray, max_side: int
) -> tuple[int, int] | None:
    """Decode a JPEG as TIGHT RGB rows (stride w*3, no canvas padding)
    straight into ``dst`` — a caller-owned flat uint8 view, typically a
    bump-allocated span of a shared ragged arena — and return the decoded
    (h, w), or None on any failure (caller falls back to PIL). The C side
    validates the span's capacity before any write (an overrun would
    corrupt a NEIGHBORING image's bytes) and releases the GIL for the
    duration."""
    lib = _load()
    if lib is None or dst.dtype != np.uint8 or not dst.flags["C_CONTIGUOUS"]:
        return None
    oh = ctypes.c_int()
    ow = ctypes.c_int()
    rc = lib.twd_decode_jpeg_packed(
        data,
        len(data),
        dst.ctypes.data_as(ctypes.POINTER(ctypes.c_ubyte)),
        dst.nbytes,
        max_side,
        ctypes.byref(oh),
        ctypes.byref(ow),
    )
    if rc != 0:
        return None
    _count_native_decode()
    return oh.value, ow.value


def decode_into_row(
    data: bytes, row: np.ndarray, canvas: int, wire: str, trailer: bool = False
) -> tuple[int, int] | None:
    """Decode a JPEG directly into ``row`` — a caller-owned uint8 buffer,
    typically a leased staging-slab row view — and return the valid
    (h, w), or None on any decode failure (caller falls back to PIL).

    The C side validates the slot's capacity before writing (an overrun
    would corrupt a neighboring request's row) and, with ``trailer``,
    also writes the packed wire's 4-byte big-endian (h, w) trailer after
    the canvas bytes. The call releases the GIL, so worker threads decode
    into one shared slab in parallel.
    """
    lib = _load()
    if lib is None or row.dtype != np.uint8 or not row.flags["C_CONTIGUOUS"]:
        return None
    oh = ctypes.c_int()
    ow = ctypes.c_int()
    rc = lib.twd_decode_jpeg_slot(
        data,
        len(data),
        row.ctypes.data_as(ctypes.POINTER(ctypes.c_ubyte)),
        row.nbytes,
        canvas,
        1 if wire == "yuv420" else 0,
        1 if trailer else 0,
        ctypes.byref(oh),
        ctypes.byref(ow),
    )
    if rc != 0:
        return None
    _count_native_decode()
    return oh.value, ow.value


def _decode_native(
    data: bytes, buckets: tuple[int, ...], wire: str
) -> tuple[np.ndarray, tuple[int, int], tuple[int, int]] | None:
    plan = plan_decode(data, buckets, wire)
    if plan is None:
        return None
    s, shape, orig = plan
    out = np.empty(shape, np.uint8)
    hw = decode_into_row(data, out, s, wire)
    if hw is None:
        return None
    return out, hw, orig


def decode_to_canvas(
    data: bytes, buckets: tuple[int, ...], wire: str = "rgb"
) -> tuple[np.ndarray, tuple[int, int], tuple[int, int]]:
    """Image bytes → (staged canvas, valid (h, w), original (h, w)).

    Native path for JPEGs; PIL + numpy packing for everything else. The
    original (pre-downscale) dimensions let callers map normalized model
    outputs (detection boxes) back to source-image pixel coordinates.

    Quality note: the native path downscales oversized JPEGs in the DCT
    domain, which only offers power-of-two factors (1/2, 1/4, 1/8). An
    image between 1× and 2× the top bucket therefore decodes to *below*
    the bucket (e.g. 600px → 300px with a 512 bucket) where the PIL
    fallback would resize to 512 exactly. Harmless while the top bucket
    comfortably exceeds the model input size — the device resize samples
    from the valid region either way — but it is a small, silent quality
    divergence between the two paths for borderline-oversized uploads.
    """
    got = _decode_native(data, buckets, wire)
    if got is not None:
        return got
    from ..ops.image import decode_image, pad_to_canvas, rgb_to_yuv420_canvas

    img = decode_image(data)
    canvas, hw = pad_to_canvas(img, buckets)
    if wire == "yuv420":
        canvas = rgb_to_yuv420_canvas(canvas)
    return canvas, hw, (img.shape[0], img.shape[1])
