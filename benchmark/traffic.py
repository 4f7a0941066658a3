"""The one traffic generator: a mix file plus ``--seed`` in, requests out.

A mix (``benchmark/traffic/<name>.json``) is data: the loop (``closed``
with ``clients``, or ``open`` with ``rate_per_s``), the files per request,
and the table of image sizes. This module turns it into

- a *deck* of image shapes with the table's exact proportions, shuffled by
  the seed, so that every seed sends the same set of sizes in another order;
- for an open loop, a schedule of due times: the exponential distribution's
  own quantiles as gaps (a Poisson process with no luck in it), shuffled by
  the seed, so that every seed offers the same arrivals in another order;
- a corpus of base JPEGs synthesised from the seed, a few per shape, and for
  every image sent a *variant* of its base that no other image of the run
  shares: the low-frequency entries of the luminance quantisation table are
  patched in place, which changes the decoded pixels (the response cache
  keys on them) without re-encoding anything.

Nothing here knows a cell's or a mix's name.
"""

from __future__ import annotations

import io
import json
import threading
from concurrent.futures import ThreadPoolExecutor
from dataclasses import dataclass
from pathlib import Path

import numpy as np

# Zigzag positions of the luminance table that a variant patches (the first
# six AC terms; DC stays, so brightness does), and how many values each
# takes: 4**6 = 4096 variants of one base image.
PATCH_POSITIONS = (1, 2, 3, 4, 5, 6)
PATCH_RADIX = 4
VARIANTS_PER_BASE = PATCH_RADIX ** len(PATCH_POSITIONS)
BOUNDARY = "twdbench"


@dataclass(frozen=True)
class Mix:
    """A traffic file, validated."""

    loop: str
    clients: int
    rate_per_s: float
    files_per_request: int
    quality: int
    shapes: tuple[tuple[int, int], ...]   # (h, w), one entry per deck card
    bases_per_shape: int
    timeout_s: float

    @classmethod
    def load(cls, path: Path) -> "Mix":
        d = json.loads(Path(path).read_text())
        loop = d["loop"]
        if loop not in ("closed", "open"):
            raise ValueError(f"{path}: loop must be 'closed' or 'open', got {loop!r}")
        if loop == "open" and not d.get("rate_per_s", 0) > 0:
            raise ValueError(f"{path}: an open loop needs rate_per_s > 0")
        img = d["images"]
        deck = []
        for long_side, n_long in img["long_side_px"]:
            for (ah, aw), n_aspect in img["aspect_h_w"]:
                short = int(round(long_side * min(ah, aw) / max(ah, aw)))
                hw = (long_side, short) if ah > aw else (short, long_side)
                deck += [hw] * (int(n_long) * int(n_aspect))
        if not deck:
            raise ValueError(f"{path}: the size table is empty")
        return cls(loop=loop, clients=int(d["clients"]), rate_per_s=float(d.get("rate_per_s", 0.0)),
                   files_per_request=int(d.get("files_per_request", 1)),
                   quality=int(img["quality"]), shapes=tuple(deck),
                   bases_per_shape=int(img.get("bases_per_shape", 4)),
                   timeout_s=float(d.get("timeout_s", 30.0)))


def schedule(rate_per_s: float, seconds: float, seed: int) -> np.ndarray:
    """Due times in [0, seconds): a Poisson process at ``rate_per_s`` whose
    gaps are the exponential quantiles at (i + 0.5) / n, in an order drawn
    from the seed. Every seed gets the same gaps, so the same count."""
    n = int(round(rate_per_s * seconds))
    gaps = -np.log1p(-(np.arange(n) + 0.5) / n) / rate_per_s
    rs = np.random.Generator(np.random.PCG64([seed, 2]))
    due = np.cumsum(rs.permutation(gaps))
    return due[due < seconds]


def synth_image(rs: np.random.Generator, h: int, w: int) -> np.ndarray:
    """A photo-like h x w RGB image: smooth colour fields at three scales
    and a little sensor noise, so that a JPEG of it has a photograph's
    bytes per pixel and every 8 x 8 block has low-frequency content."""
    from PIL import Image

    img = np.zeros((h, w, 3), np.float32)
    for cells, amp in ((4, 70.0), (24, 35.0), (160, 14.0)):
        gh, gw = max(2, cells * h // max(h, w)), max(2, cells * w // max(h, w))
        field = rs.normal(0.0, amp, (gh, gw, 3)).astype(np.float32)
        for c in range(3):
            img[..., c] += np.asarray(
                Image.fromarray(field[..., c], "F").resize((w, h), Image.BICUBIC))
    tile = rs.normal(0.0, 3.0, (256, 256, 3)).astype(np.float32)
    img += np.tile(tile, (-(-h // 256), -(-w // 256), 1))[:h, :w]
    return (img + 127.5).clip(0, 255).astype(np.uint8)


def encode_jpeg(pixels: np.ndarray, quality: int) -> bytes:
    from PIL import Image

    buf = io.BytesIO()
    Image.fromarray(pixels).save(buf, "JPEG", quality=quality)
    return buf.getvalue()


def luma_table_offset(jpeg: bytes) -> int:
    """Offset of the first entry of quantisation table 0 (8-bit entries, in
    zigzag order) in a baseline JPEG."""
    i = 2
    while i + 4 <= len(jpeg) and jpeg[i] == 0xFF:
        marker, length = jpeg[i + 1], int.from_bytes(jpeg[i + 2:i + 4], "big")
        if marker == 0xDB:
            j = i + 4
            while j < i + 2 + length:
                precision, table = jpeg[j] >> 4, jpeg[j] & 15
                if table == 0:
                    if precision:
                        raise ValueError("16-bit quantisation table: not a baseline JPEG")
                    return j + 1
                j += 1 + 64 * (precision + 1)
        if marker == 0xDA:
            break
        i += 2 + length
    raise ValueError("no quantisation table 0 before the scan")


@dataclass
class Base:
    jpeg: bytes
    hw: tuple[int, int]
    table: int          # luma_table_offset(jpeg)
    used: int = 0       # variants handed out so far


def variant(base: Base, k: int) -> bytes:
    """The ``k``-th variant of ``base``: ``k`` written in radix
    ``PATCH_RADIX`` onto the patched table entries. Variant 0 is the base."""
    if not 0 <= k < VARIANTS_PER_BASE:
        raise ValueError(f"variant {k}: one base image has {VARIANTS_PER_BASE}")
    body = bytearray(base.jpeg)
    for pos in PATCH_POSITIONS:
        k, digit = divmod(k, PATCH_RADIX)
        body[base.table + pos] = min(255, base.jpeg[base.table + pos] + digit)
    return bytes(body)


class Corpus:
    """Base JPEGs by shape, and the hand that deals unique variants."""

    def __init__(self, mix: Mix, seed: int, threads: int = 8):
        self.mix = mix
        shapes = sorted(set(mix.shapes))
        jobs = [(hw, b) for hw in shapes for b in range(mix.bases_per_shape)]

        def build(job):
            (h, w), b = job
            rs = np.random.Generator(np.random.PCG64([seed, 3, h, w, b]))
            jpeg = encode_jpeg(synth_image(rs, h, w), mix.quality)
            return Base(jpeg, (h, w), luma_table_offset(jpeg))

        # PIL and numpy release the interpreter lock in resize and encode.
        with ThreadPoolExecutor(threads) as pool:
            built = list(pool.map(build, jobs))
        self.bases: dict[tuple[int, int], list[Base]] = {hw: [] for hw in shapes}
        for (hw, _), base in zip(jobs, built):
            self.bases[hw].append(base)

    def deal(self, hw: tuple[int, int]) -> tuple[Base, int]:
        """The least-used base of this shape and its next unused variant.
        Not thread-safe: ``Source.take`` calls it under its lock."""
        base = min(self.bases[hw], key=lambda b: b.used)
        k = base.used
        if k >= VARIANTS_PER_BASE:
            raise RuntimeError(
                f"shape {hw}: all {VARIANTS_PER_BASE * len(self.bases[hw])} unique variants are "
                "dealt; raise bases_per_shape in the traffic file")
        base.used += 1
        return base, k


@dataclass(frozen=True)
class Request:
    """One request, ready to build: its images as (base, variant)."""

    index: int
    images: tuple[tuple[Base, int], ...]

    def body(self) -> tuple[bytes, str]:
        if len(self.images) == 1:
            return variant(*self.images[0]), "image/jpeg"
        parts = [
            (f"--{BOUNDARY}\r\nContent-Disposition: form-data; name=\"f{i}\"; "
             f"filename=\"f{i}.jpg\"\r\nContent-Type: image/jpeg\r\n\r\n").encode()
            + variant(base, k) + b"\r\n" for i, (base, k) in enumerate(self.images)]
        return b"".join(parts) + f"--{BOUNDARY}--\r\n".encode(), f"multipart/form-data; boundary={BOUNDARY}"


class Source:
    """The run's requests in order: the seed's deck, dealt on demand (a
    closed loop cannot say in advance how many it will send)."""

    def __init__(self, corpus: Corpus, seed: int):
        self.corpus, self.seed = corpus, seed
        self._lock = threading.Lock()
        self._deck: list[tuple[int, int]] = []
        self._decks = 0
        self._index = 0

    def take(self) -> Request:
        per = self.corpus.mix.files_per_request
        with self._lock:
            while len(self._deck) < per:
                rs = np.random.Generator(np.random.PCG64([self.seed, 1, self._decks]))
                shapes = self.corpus.mix.shapes
                self._deck += [shapes[i] for i in rs.permutation(len(shapes))]
                self._decks += 1
            hws, self._deck = self._deck[:per], self._deck[per:]
            req = Request(self._index, tuple(self.corpus.deal(hw) for hw in hws))
            self._index += 1
            return req
