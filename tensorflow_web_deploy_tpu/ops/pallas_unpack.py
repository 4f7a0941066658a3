"""Mosaic (Pallas-TPU) ragged unpack: tight arena rows → canvas planes.

The XLA formulation in ops/image.py (``vmap(vmap(dynamic_slice))``) lowers
on a TPU to a serial ``while`` of one 3s-byte slice and one update per
canvas row — 131,072 trips for a canvas-4096 × batch-32 batch, 5.7 us a
trip, and a trace event each. This kernel does the same move a block of
``R`` canvas rows at a time, as one custom call.

Two facts of the device shape it (both found compiling for a v5e):

* uint8 ``[K, s, s, 3]`` canvases lie PLANAR on the device — layout
  ``{2,1,3,0:T(8,128)(4,1)}``: channel-major, x on the lanes, four rows to
  a 32-bit word — so the kernel writes planes ``[K, 3, s, s]`` and the
  caller's transpose back is a re-view, not a copy. (Interleaved output
  would leave XLA the de-interleave: 45 ms a canvas-4096 × 32 batch.)
* bytes cannot become words on the device (the re-view's minor dimension
  of 4 pads to 128 lanes), so the arena arrives as little-endian
  ``uint32`` words — a numpy view on the host — and is read ``[M, 128]``:
  512-byte lane rows, the same bytes in linear order.

Image ``k`` lies tight from byte ``off``; ``R`` consecutive rows of it are
one contiguous source range of ``R·3w ≤ R·3s`` bytes. One grid step
(image, row block):

1. waits for its window of the arena — one static-size DMA, HBM → VMEM,
   from the block's first byte aligned down to 8 lane rows (4 KB), which
   the step before it started — and starts the next step's;
2. stage 1, per canvas row, re-strides from ``3w`` to ``3s`` bytes: the
   row's first byte sits ``p = 512a + 4l + b`` bytes into the window; a
   load of ``T = 3s/512`` lane rows from sublane ``a`` and one from
   ``a+1``, a select on the lane index (the lanes that wrap take the
   latter), one lane roll by ``l``, and a mask to zero past byte ``3w``.
   Where rows do not start on words (``b ≠ 0``: an odd width somewhere)
   the same one lane on, and two shifts and an or, join the halves;
3. stage 2, per 32 rows × 512 pixels, de-interleaves: a sublane-strided
   load brings rows ``4σ+ρ`` of one lane row onto the sublanes, a 4×4 byte
   transpose across the four ``ρ`` makes the words of a uint8 tile (one
   byte position of four rows), and two lane gathers a plane and 128
   pixels pick each pixel's word.

The loops inside the kernel have no branch in their bodies, and stage 1's
take four rows a trip: on a v5e a row costs its chain of scalar address,
load, roll and store, and four chains overlap (3.2 ms of stage 1 for a
canvas-4096 × batch-32 batch against 11.6 with two rows and a branch).

Rows ``y ≥ h``, pixel groups past ``w`` and holes are written as zeros
without being computed: whole canvases are written, always.

``interpret=True`` runs the kernel through the Pallas interpreter on the
CPU (tests/test_ragged.py); what Mosaic accepts is pinned by
tests/test_tpu_compile.py; that it runs on the chip where it applies, by
chip_smoke.py. Bit for bit against the XLA formulation on the chip itself
was a run by hand (PERF.md section 6, PR 28).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANES = 128
# Bytes of window, of re-strided rows and of output one grid step holds
# (each R·3s; the output double-buffered by the pipeline): 768 KB is 64
# rows of a 4096 canvas.
_BLOCK_BYTES = 768 << 10


def kernel_fits(s: int) -> bool:
    """The kernel's one shape condition: a canvas row (3s bytes) is a whole
    number of 512-byte lane rows — canvas 512 and every multiple of it."""
    return s >= 512 and s % 512 == 0


def row_block(s: int) -> int:
    """Canvas rows per grid step: the largest divisor of ``s`` that is a
    multiple of 32 (u8 rows pack 32 to a tile), at most half the canvas
    (the window then fits the smallest arena, one canvas) and at most
    ``_BLOCK_BYTES`` of rows."""
    cap = min(s // 2, _BLOCK_BYTES // (3 * s))
    return max(r for r in range(32, cap + 1, 32) if s % r == 0)


def _transpose_bytes(x0, x1, x2, x3):
    """Four words → four words: byte ``j`` of ``x[ρ]`` becomes byte ``ρ`` of
    ``y[j]`` (a 4×4 byte transpose in every lane, by two butterflies)."""
    even = jnp.uint32(0x00FF00FF)
    low = jnp.uint32(0x0000FFFF)
    t0 = (x0 & even) | ((x1 & even) << 8)
    t1 = ((x0 >> 8) & even) | (x1 & ~even)
    t2 = (x2 & even) | ((x3 & even) << 8)
    t3 = ((x2 >> 8) & even) | (x3 & ~even)
    return ((t0 & low) | (t2 << 16), (t1 & low) | (t3 << 16),
            (t0 >> 16) | (t2 & ~low), (t1 >> 16) | (t3 & ~low))


def _plane_taps(c: int, m: int):
    """Where plane ``c``'s bytes of pixels ``128m .. 128m+127`` of a
    512-pixel group lie in the group's 384 words: per lane, the source
    lane and whether the byte is the word's fourth (byte 3); then the two
    128-word registers ``(v_lo, v_hi)`` the chunk's 96 words span, and the
    first lane it reads of ``v_lo`` (of ``v_hi`` it reads the lanes below)."""
    i = jax.lax.broadcasted_iota(jnp.int32, (8, LANES), 1)
    beta = 384 * m + 3 * i + c
    first = 96 * m
    return ((beta >> 2) & (LANES - 1), (beta & 3) == 3,
            first & (LANES - 1), first >> 7, (first + 95) >> 7)


def _unpack_kernel(meta_ref, arena_ref, out_ref, win_ref, rows_ref, sem,
                   *, s, rb, m_rows):
    t = 3 * s // 512  # lane rows per canvas row
    wr = rb * t + 8  # window lane rows
    nblk = s // rb
    step = pl.program_id(0) * nblk + pl.program_id(1)

    def block(i):
        """Grid step ``i``'s rows with pixels, first byte, row pitch and
        window start."""
        k, y0 = i // nblk, (i % nblk) * rb
        off, h, w = meta_ref[4 * k], meta_ref[4 * k + 1], meta_ref[4 * k + 2]
        nv = jnp.where(meta_ref[4 * k + 3] > 0, jnp.clip(h - y0, 0, rb), 0)
        w3 = w * 3
        p0 = off + y0 * w3
        # Aligned down to 8 lane rows, and back from the arena's end so
        # that the static-size copy stays inside it (every row with pixels
        # ends inside the arena, so it ends in the window).
        r0 = jnp.minimum((p0 >> 12) << 3, m_rows - wr)
        return nv, p0, w3, pl.multiple_of(r0, 8)

    def window(i, r0):
        slot = i % 2
        return pltpu.make_async_copy(
            arena_ref.at[pl.ds(r0, wr)], win_ref.at[slot, pl.ds(0, wr)],
            sem.at[slot])

    nv, p0, w3, r0 = block(step)

    # The grid runs in order on one core: each step starts the next step's
    # copy before it waits for its own, which the step before started.
    @pl.when((step == 0) & (nv > 0))
    def _():
        window(step, r0).start()

    @pl.when(step + 1 < pl.num_programs(0) * nblk)
    def _():
        nv1, _, _, r1 = block(step + 1)

        @pl.when(nv1 > 0)
        def _():
            window(step + 1, r1).start()

    @pl.when(nv == 0)
    def _():
        out_ref[...] = jnp.zeros(out_ref.shape, out_ref.dtype)

    @pl.when((nv > 0) & (nv < rb))
    def _():
        rows_ref[...] = jnp.zeros(rows_ref.shape, rows_ref.dtype)

    @pl.when(nv > 0)
    def _():
        win = win_ref.at[step % 2]
        # Byte mask of a canvas row's words: all of a word below 3w, part
        # of the word that straddles it, nothing past it.
        lane = jax.lax.broadcasted_iota(jnp.int32, (t, LANES), 1)
        j = jax.lax.broadcasted_iota(jnp.int32, (t, LANES), 0) * LANES + lane
        nb = jnp.clip(w3 - 4 * j, 0, 4)
        ones = jnp.uint32(0xFFFFFFFF)
        keep = jnp.where(nb >= 4, ones,
                         ~(ones << (8 * jnp.minimum(nb, 3)).astype(jnp.uint32)))
        window(step, r0).wait()

        # Stage 1, a canvas row at a time: the row's words, in stream
        # order over (lane row, lane), to rows_ref[y·t : (y+1)·t].
        def put_row(y, straddle):
            p = p0 + y * w3 - (r0 << 9)
            q = p >> 2
            a, l = q >> 7, q & (LANES - 1)
            lo_rows = win[pl.ds(a, t), :]
            hi_rows = win[pl.ds(a + 1, t), :]

            def stream(l):  # l, or l+1 for the words one on
                # Words q+j of the stream, q = 128a + l: lane l+lane of row
                # a+t, wrapping into row a+t+1 — the wrapped lanes picked
                # first, then one roll. A row reads 3s bytes whatever its
                # 3w: past the window (a narrow image at the arena's end)
                # it reads the scratch's slack rows, which `keep` masks
                # like everything else past 3w.
                return pltpu.roll(jnp.where(lane >= l, lo_rows, hi_rows),
                                  (LANES - l) & (LANES - 1), 1)

            word = stream(l)
            if straddle:
                # b bytes into the word: the top 4-b bytes of word q+j
                # under the bottom b of word q+j+1 (in two steps, so that
                # b = 0 shifts the latter out).
                sh = (8 * (p & 3)).astype(jnp.uint32)
                nxt = jnp.where(l == LANES - 1, hi_rows, stream((l + 1) & (LANES - 1)))
                word = (word >> sh) | ((nxt << 8) << (24 - sh))
            rows_ref[pl.ds(pl.multiple_of(y * t, t), t), :] = word & keep

        def rows(straddle):
            def trip(i, carry):
                # Four independent rows a trip, for the scheduler; the
                # last trip writes the last row again.
                for r in range(4):
                    put_row(jnp.minimum(4 * i + r, nv - 1), straddle)
                return carry

            jax.lax.fori_loop(0, (nv + 3) // 4, trip, 0)

        # Every row of the block starts on a word where the block does and
        # the pitch is whole words: images whose width is a multiple of 4
        # behind such images, which is what cameras write.
        on_words = ((p0 | w3) & 3) == 0
        pl.when(on_words)(lambda: rows(False))
        pl.when(~on_words)(lambda: rows(True))

        # Stage 2, 32 canvas rows x 512 pixels (384 words) at a time:
        # interleaved words -> planes. A strided load puts rows 4σ+ρ of one
        # lane row on the sublanes σ; a byte transpose across the four ρ
        # makes words of one byte position j of four rows, which is what a
        # u8 tile's word holds. Plane c's byte of pixel x is byte j = p & 3
        # of word k = p >> 2, p = 3x + c, and j = (c - k) mod 3 unless
        # j = 3: so one merged source per plane (by k mod 3) and the j = 3
        # words are all a lane gather has to reach into.
        taps = {(c, m): _plane_taps(c, m) for c in range(3) for m in range(4)}
        lane8 = jax.lax.broadcasted_iota(jnp.int32, (8, LANES), 1)
        # Which byte position a plane's merged source takes from word
        # k = 128v + lane of the group: k mod 3 = (2v + lane) mod 3.
        pick = [(r - lane8) % 3 for r in range(3)]
        groups = (w3 + 1535) // 1536  # 512-pixel groups that hold pixels

        def put(g, u, c, m, tile):
            y32 = pl.multiple_of(g * 32, 32)
            x0 = pl.multiple_of(u * 512 + m * LANES, LANES)
            out_ref[0, c, pl.ds(y32, 32), pl.ds(x0, LANES)] = tile

        def planes(g, u):
            base = g * (32 * t) + 3 * u
            y = [_transpose_bytes(*(
                rows_ref[pl.ds(base + rho * t + v, 8, stride=4 * t), :]
                for rho in range(4))) for v in range(3)]
            for c in range(3):
                merged = []
                for v in range(3):
                    j = pick[(c - 2 * v) % 3]
                    merged.append(jnp.where(
                        j == 0, y[v][0], jnp.where(j == 1, y[v][1], y[v][2])))
                for m in range(4):
                    idx, fourth, first, v_lo, v_hi = taps[c, m]

                    def take(src):
                        # A chunk that spans two registers reads disjoint
                        # lanes of them: pick, then gather.
                        if v_hi == v_lo:
                            return jnp.take_along_axis(src[v_lo], idx, axis=1)
                        return jnp.take_along_axis(
                            jnp.where(lane8 >= first, src[v_lo], src[v_hi]),
                            idx, axis=1)

                    word = jnp.where(fourth, take([yv[3] for yv in y]),
                                     take(merged))
                    put(g, u, c, m, pltpu.bitcast(word, jnp.uint8))

        def zeros(g, u):
            for c, m in taps:
                put(g, u, c, m, jnp.zeros((32, LANES), jnp.uint8))

        def rows32(g, carry):
            jax.lax.fori_loop(0, groups, lambda u, _: planes(g, u), None)
            jax.lax.fori_loop(groups, t // 3, lambda u, _: zeros(g, u), None)
            return carry

        jax.lax.fori_loop(0, rb // 32, rows32, 0)


@functools.partial(jax.jit, static_argnames=("s", "interpret"))
def unpack_planes(words, meta, *, s: int, interpret: bool = False):
    """Arena words ``uint32 [n]`` (``n`` a multiple of 1024, at least one
    canvas) + ``meta`` ``int32 [K, 4]`` → canvas planes ``uint8 [K, 3, s,
    s]``, zero outside each image's valid region."""
    k = meta.shape[0]
    t = 3 * s // 512
    rb = row_block(s)
    m_rows = words.shape[0] // LANES
    kernel = functools.partial(_unpack_kernel, s=s, rb=rb, m_rows=m_rows)
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(k, s // rb),
            in_specs=[pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=pl.BlockSpec((1, 3, rb, s),
                                   lambda i, j, meta: (i, 0, j, 0)),
            # Two windows, each with a canvas row and one of slack (stage 1).
            scratch_shapes=[pltpu.VMEM((2, rb * t + 8 + t + 8, LANES), jnp.uint32),
                            pltpu.VMEM((rb * t, LANES), jnp.uint32),
                            pltpu.SemaphoreType.DMA((2,))],
        ),
        out_shape=jax.ShapeDtypeStruct((k, 3, s, s), jnp.uint8),
        # In order, on one core: a step waits for the copy the step before
        # it started.
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
        interpret=interpret,
    )(meta.reshape(-1), words.reshape(m_rows, LANES))
