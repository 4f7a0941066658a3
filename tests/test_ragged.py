"""Ragged wire (ISSUE 14): packed byte slabs + on-device unpack/resize.

Golden parity is the load-bearing property: `unpack_ragged` reconstructs
the exact canvases the host-padded path would have shipped, so a ragged
engine's outputs must agree with the classic path bit-for-bit (same jit
program from the canvases on). The packing-identity tests assert the
batcher half: an image packed into a shared arena answers exactly like
the same image submitted solo.
"""

import io

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tensorflow_web_deploy_tpu.ops.image import fit_to_bucket, unpack_ragged
from tensorflow_web_deploy_tpu.serving.batcher import Batcher
from tensorflow_web_deploy_tpu.serving.engine import InferenceEngine
from tensorflow_web_deploy_tpu.utils.config import ModelConfig, ServerConfig

# Tiny configs per zoo architecture: enough layers to be the real model,
# small enough for the 8-device CPU mesh. Inception's VALID stem needs
# >= 75 px of model input.
_ZOO = {
    "mobilenet_v2": dict(task="classify", input_size=(48, 48)),
    "resnet50": dict(task="classify", input_size=(48, 48)),
    "inception_v3": dict(task="classify", input_size=(96, 96)),
    "ssd_mobilenet": dict(task="detect", input_size=(96, 96)),
}


def _cfg(name, ragged=True, canvas=96, batch=8, **kw):
    spec = _ZOO[name]
    kw.setdefault("wire_format", "rgb")
    return ServerConfig(
        model=ModelConfig(
            name=name, source="native", task=spec["task"], zoo_width=0.25,
            zoo_classes=12, input_size=spec["input_size"],
            preprocess="inception", topk=3,
        ),
        canvas_buckets=(canvas,), batch_buckets=(batch,), max_batch=batch,
        ragged=ragged, warmup=False, **kw,
    )


def _mixed_images(rng, canvas, n=4):
    """n images, none larger than the canvas, sizes deliberately mixed:
    full-bucket, landscape, portrait, tiny."""
    dims = [(canvas, canvas), (canvas * 3 // 4, canvas // 2),
            (canvas // 2, canvas * 2 // 3), (17, 23)]
    return [
        (rng.rand(h, w, 3) * 255).astype(np.uint8)
        for h, w in (dims * ((n + 3) // 4))[:n]
    ]


def _padded(imgs, canvas):
    canvases = np.zeros((len(imgs), canvas, canvas, 3), np.uint8)
    hws = np.ones((len(imgs), 2), np.int32)
    for i, im in enumerate(imgs):
        h, w = im.shape[:2]
        canvases[i, :h, :w] = im
        hws[i] = (h, w)
    return canvases, hws


def _pack(engine, imgs, canvas):
    slab = engine.acquire_ragged(len(imgs), canvas)
    for im in imgs:
        h, w = im.shape[:2]
        idx, view = slab.alloc(h * w * 3)
        view[:] = im.reshape(-1)
        slab.write_hw(idx, (h, w))
    return slab


# ----------------------------------------------------------------- unpack op


def test_unpack_ragged_reconstructs_padded_canvases(rng):
    s, imgs = 32, _mixed_images(rng, 32, n=3)
    row_bytes = s * s * 3
    arena = np.zeros(3 * row_bytes, np.uint8)
    meta = np.zeros((3, 4), np.int32)
    off = 0
    for i, im in enumerate(imgs):
        h, w = im.shape[:2]
        arena[off:off + im.size] = im.reshape(-1)
        meta[i] = (off, h, w, 1)
        off += im.size
    canvases, hws = unpack_ragged(arena, meta, s)
    ref_c, ref_hw = _padded(imgs, s)
    np.testing.assert_array_equal(np.asarray(canvases), ref_c)
    np.testing.assert_array_equal(np.asarray(hws), ref_hw)


def test_unpack_ragged_tight_arena_end(rng):
    """The unpack reads each canvas row as one 3s-byte window; the last
    rows of the last image start less than 3s bytes before the end of an
    arena with no slack, and a clamped window would shift their pixels."""
    s, imgs = 32, _mixed_images(rng, 32, n=4)[1:]  # ends on the 17x23 image
    arena = np.concatenate([im.reshape(-1) for im in imgs])
    meta = np.zeros((3, 4), np.int32)
    off = 0
    for i, im in enumerate(imgs):
        meta[i] = (off, im.shape[0], im.shape[1], 1)
        off += im.size
    assert off == arena.size
    canvases, hws = unpack_ragged(arena, meta, s)
    ref_c, ref_hw = _padded(imgs, s)
    np.testing.assert_array_equal(np.asarray(canvases), ref_c)
    np.testing.assert_array_equal(np.asarray(hws), ref_hw)


# ------------------------------------------------- unpack op: the kernel
#
# ops/pallas_unpack.py through the Pallas interpreter, bit for bit against
# the XLA formulation above (what the chip's Mosaic accepts of it is
# tests/test_tpu_compile.py's; what it computes there, chip_smoke.py's).


def _deck(s, bucket, rows):
    """(h, w) or None (a hole) for each of ``bucket`` slots, filling
    ``rows`` canvases of arena to its last byte: odd and even widths (so
    every byte shift 0-3 and word shifts across a 128-lane boundary
    occur), a full canvas, a one-row image, a hole, and a balance that
    ends the last image on the arena's end."""
    if bucket == 1:
        return [(s, s)]
    head = [(37, s - 11), (s, s), None, (5, 3), (s // 2 + 1, s // 3 + 2)]
    if bucket > 8:
        head += [(1, 1), (s // 8, s - 1), None, (s - 1, s // 2 - 3), (3, s),
                 None, (s // 4, s // 4 + 1)]
    left = rows * s * s - sum(h * w for h, w in filter(None, head))
    tail = [(1, left % s)] if left % s else []
    left -= left % s
    while left >= s * s:
        tail.append((s, s))
        left -= s * s
    if left:
        tail.append((left // s, s))
    assert len(head) + len(tail) <= bucket, (s, bucket, rows)
    return head + tail + [None] * (bucket - len(head) - len(tail))


# (bucket, rows shipped): a sole canvas; arenas shipped short of the bucket.
@pytest.mark.parametrize("bucket,rows", [(1, 1), (8, 3), (32, 4)])
@pytest.mark.parametrize("s", [512, 1024, 2048])
def test_unpack_kernel_matches_xla_formulation(s, bucket, rows):
    rng = np.random.RandomState(s + bucket)
    deck = _deck(s, bucket, rows)
    arena = np.zeros(rows * s * s * 3, np.uint8)
    meta = np.zeros((bucket, 4), np.int32)
    off, shifts, wraps = 0, set(), False
    for i, hw in enumerate(deck):
        if hw is None:
            continue
        h, w = hw
        arena[off:off + h * w * 3] = rng.randint(1, 256, h * w * 3)
        meta[i] = (off, h, w, 1)
        starts = off + np.arange(h) * (w * 3)
        shifts |= set(starts % 4)
        wraps |= bool(np.any((starts // 4) % 128 + (w * 3) // 4 > 128))
        off += h * w * 3
    assert off == arena.size  # the last image ends on the arena's last byte
    assert bucket == 1 or (shifts == {0, 1, 2, 3} and wraps)
    ref_c, ref_hw = jax.jit(lambda a, m: unpack_ragged(a, m, s))(arena, meta)
    got_c, got_hw = jax.jit(
        lambda a, m: unpack_ragged(a, m, s, interpret=True)
    )(arena.view(np.uint32), meta)
    assert got_c.dtype == jnp.uint8 and got_c.shape == (bucket, s, s, 3)
    # Compared on the device: 400 MB a side at canvas 2048 x 32.
    assert bool(jnp.array_equal(got_c, ref_c))
    np.testing.assert_array_equal(np.asarray(got_hw), np.asarray(ref_hw))
    # And the formulation itself against the host's pad-to-canvas, where
    # that is cheap: a full canvas, and a hole.
    i = deck.index((s, s))
    np.testing.assert_array_equal(
        np.asarray(got_c[i]),
        arena[meta[i, 0]:meta[i, 0] + s * s * 3].reshape(s, s, 3))
    if None in deck:
        assert not np.asarray(got_c[deck.index(None)]).any()


@pytest.mark.parametrize("s", [512, 2048])
def test_unpack_kernel_narrow_image_on_the_arenas_last_byte(s):
    """Stage 1 reads 3s bytes from a row's first byte whatever the row's
    width: for a narrow image that ends the arena that is past the window,
    and what it reads there must be masked, not shifted into the row."""
    rng = np.random.RandomState(s)
    deck = [(s - 1, s), (1, s - 64 * 7), (64, 7), None]
    arena = rng.randint(1, 256, s * s * 3).astype(np.uint8)
    meta = np.zeros((4, 4), np.int32)
    off = 0
    for i, (h, w) in enumerate(deck[:3]):
        meta[i] = (off, h, w, 1)
        off += h * w * 3
    assert off == arena.size
    ref_c, _ = jax.jit(lambda a, m: unpack_ragged(a, m, s))(arena, meta)
    got_c, _ = jax.jit(
        lambda a, m: unpack_ragged(a, m, s, interpret=True)
    )(arena.view(np.uint32), meta)
    assert bool(jnp.array_equal(got_c, ref_c))
    np.testing.assert_array_equal(
        np.asarray(got_c[2, :64, :7]), arena[-64 * 7 * 3:].reshape(64, 7, 3))


@pytest.mark.parametrize("s,fits,block", [
    (256, False, None), (384, False, None), (512, True, 256), (1024, True, 256),
    (1536, True, 128), (2048, True, 128), (4096, True, 64)])
def test_unpack_kernel_shapes(s, fits, block):
    """Canvas 512 and its multiples; a block divides the canvas, is whole
    u8 tiles of 32 rows and leaves the window inside a one-canvas arena."""
    from tensorflow_web_deploy_tpu.ops.pallas_unpack import kernel_fits, row_block

    assert kernel_fits(s) is fits
    if fits:
        assert row_block(s) == block and s % block == 0 and block % 32 == 0
        assert (block * 3 * s) // 512 + 8 <= s * 3 * s // 512


def test_unpack_kernel_applies_nowhere_on_the_cpu():
    """The engine ships words only where the kernel runs compiled: never
    under these tests, whatever the canvas and the mesh."""
    from tensorflow_web_deploy_tpu.ops.image import unpack_kernel_applies

    assert not any(unpack_kernel_applies(s, n) for s in (256, 512, 4096) for n in (1, 4))


def test_rows_shipped_yields_only_warmed_shapes():
    """The batcher leases top-capacity slabs and dispatch re-buckets: the
    shipped-rows quantization must follow the DISPATCH bucket, or a batch
    ships a (bucket, rows) shape that warmup never compiled and the
    request path pays the compile (found by chip_smoke.py's burst)."""
    from tensorflow_web_deploy_tpu.serving.engine import RaggedSlab

    slab = RaggedSlab(canvas_s=16, bucket=32)
    for bucket in (1, 2, 4, 8, 16, 32):
        q = max(1, bucket // 8)
        warmed = set(range(q, bucket + 1, q))  # engine._warm_executables
        for used in range(0, bucket * slab.row_bytes + 1, slab.row_bytes // 3):
            slab.used = used
            assert slab.rows_shipped(bucket) in warmed, (bucket, used)
            assert slab.rows_shipped(bucket) * slab.row_bytes >= used


def test_unpack_ragged_invalid_rows_are_1x1_zero(rng):
    s = 16
    arena = (rng.rand(s * s * 3) * 255).astype(np.uint8)
    meta = np.zeros((2, 4), np.int32)  # both rows invalid
    canvases, hws = unpack_ragged(arena, meta, s)
    assert np.asarray(canvases).sum() == 0
    np.testing.assert_array_equal(np.asarray(hws), np.ones((2, 2), np.int32))


def test_fit_to_bucket(rng):
    small = (rng.rand(20, 30, 3) * 255).astype(np.uint8)
    tight, hw, s = fit_to_bucket(small, (64,))
    assert s == 64 and hw == (20, 30)
    np.testing.assert_array_equal(tight, small)  # no resize below bucket
    big = (rng.rand(200, 100, 3) * 255).astype(np.uint8)
    tight, hw, s = fit_to_bucket(big, (64,))
    assert s == 64 and max(hw) == 64 and tight.shape[:2] == hw
    assert tight.flags["C_CONTIGUOUS"] and tight.dtype == np.uint8


# ------------------------------------------------------------- golden parity


@pytest.mark.parametrize("name", sorted(_ZOO))
def test_golden_parity_ragged_vs_host_path(name, rng):
    """All four zoo presets: the ragged dispatch (packed arena, on-device
    unpack) answers exactly like the classic host-padded path — top-1
    agreement and logit equality within float tolerance."""
    engine = InferenceEngine(_cfg(name))
    try:
        assert engine.ragged
        imgs = _mixed_images(rng, 96, n=4)
        canvases, hws = _padded(imgs, 96)
        ref = engine.run_batch(canvases, hws)
        slab = _pack(engine, imgs, 96)
        out = engine.fetch_outputs(engine.dispatch_ragged(slab, len(imgs)))
        assert len(ref) == len(out)
        for a, b in zip(ref, out):
            a, b = np.asarray(a), np.asarray(b)
            assert a.shape == b.shape
            np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-6)
        if _ZOO[name]["task"] == "classify":
            scores_r, idx_r = (np.asarray(x) for x in ref)
            scores_p, idx_p = (np.asarray(x) for x in out)
            np.testing.assert_array_equal(idx_r[:, 0], idx_p[:, 0])
    finally:
        engine.close()


def test_ragged_partial_arena_hole_parity(rng):
    """A slab with a hole (expired lease padded to 1x1) still answers the
    committed row exactly like a solo classic batch."""
    engine = InferenceEngine(_cfg("mobilenet_v2"))
    try:
        img = _mixed_images(rng, 96, n=1)[0]
        canvases, hws = _padded([img], 96)
        ref = engine.run_batch(canvases, hws)
        slab = engine.acquire_ragged(2, 96)
        i0, v0 = slab.alloc(img.size)
        v0[:] = img.reshape(-1)
        slab.write_hw(i0, img.shape[:2])
        i1, _ = slab.alloc(3)
        slab.write_hw(i1, (1, 1))  # the batcher's hole padding
        out = engine.fetch_outputs(engine.dispatch_ragged(slab, 2))
        for a, b in zip(ref, out):
            np.testing.assert_array_equal(np.asarray(a)[0], np.asarray(b)[0])
    finally:
        engine.close()


def _stage_holed(slab, imgs):
    """imgs[0], a hole (a lease that died before commit: valid stays 0),
    imgs[1]: the batch ends on a short last row."""
    for im in (imgs[0], None, imgs[1]):
        h, w = (3, 5) if im is None else im.shape[:2]
        idx, view = slab.alloc(h * w * 3)
        if im is not None:
            view[:] = im.reshape(-1)
            slab.write_hw(idx, (h, w))


def test_dirty_arena_answers_bit_for_bit_like_a_fresh_one(rng):
    """A pooled arena comes back as its last batch left it and is not
    zeroed (1.6 GB at canvas 4096 x batch 32). ``arm`` resets the cursors
    and the meta table, a hole keeps valid = 0, and the unpack reads only
    what the meta table bounds: the canvases and the served top-k of a
    batch staged into an arena full of 0xFF equal, bit for bit, those of
    the same batch in a newly allocated one."""
    engine = InferenceEngine(_cfg("mobilenet_v2"))
    try:
        s, n = 96, 3
        imgs = [_mixed_images(rng, s, n=2)[1], (rng.rand(17, 23, 3) * 255).astype(np.uint8)]

        def serve(slab):
            _stage_holed(slab, imgs)
            rows = slab.rows_shipped(engine.pick_batch_bucket(n))
            canvases, hws = unpack_ragged(
                slab.buf[: rows * slab.row_bytes].copy(), slab.meta.copy(), s)
            outs = engine.fetch_outputs(engine.dispatch_ragged(slab, n))
            return np.asarray(canvases), np.asarray(hws), [np.asarray(o) for o in outs]

        before = engine.staging_stats()
        fresh = engine.acquire_ragged(n, s)
        assert not fresh.buf.any()  # newly allocated: zeros
        want_c, want_hw, want = serve(fresh)

        dirty = engine.acquire_ragged(n, s)
        assert dirty is fresh  # the pool's
        dirty.buf[:] = 0xFF
        dirty.meta[:] = 0x7F7F7F7F
        engine.release_staging(dirty)
        again = engine.acquire_ragged(n, s)
        after = engine.staging_stats()
        assert again is dirty and again.buf.min() == 0xFF  # reused, not zeroed
        assert not again.meta.any() and again.used == 0 and again.slots == 0
        assert after["slab_acquires_total"] - before["slab_acquires_total"] == 3
        assert after["slab_allocs_total"] - before["slab_allocs_total"] == 1
        got_c, got_hw, got = serve(again)

        np.testing.assert_array_equal(got_c, want_c)
        np.testing.assert_array_equal(got_hw, want_hw)
        assert not got_c[1].any() and tuple(got_hw[1]) == (1, 1)  # the hole
        ref_c, ref_hw = _padded(imgs, s)
        np.testing.assert_array_equal(got_c[[0, 2]], ref_c)
        np.testing.assert_array_equal(got_hw[[0, 2]], ref_hw)
        assert len(got) == len(want)
        for a, b in zip(got, want):
            np.testing.assert_array_equal(a, b)  # scores and indices, all rows
    finally:
        engine.close()


def test_unpack_kernel_never_reads_dirty_slack():
    """The same for the Mosaic kernel (through the interpreter): an arena
    shipped with slack behind its last image and a hole in it unpacks to
    the same canvases whether the unused bytes are zeros or 0xFF."""
    s, bucket, rows = 512, 8, 3
    rng = np.random.RandomState(33)
    deck = [(37, s - 11), None, (s // 2 + 1, s // 3 + 2), (5, 3)]
    meta = np.zeros((bucket, 4), np.int32)
    clean = np.zeros(rows * s * s * 3, np.uint8)
    dirty = np.full(rows * s * s * 3, 0xFF, np.uint8)
    off = 0
    for i, hw in enumerate(deck):
        h, w = hw or (9, 7)  # a hole's bytes were allocated, never committed
        if hw is not None:
            px = rng.randint(1, 256, h * w * 3)
            clean[off:off + px.size] = dirty[off:off + px.size] = px
            meta[i] = (off, h, w, 1)
        else:
            meta[i, 0] = off
        off += h * w * 3
    assert off < clean.size // 2  # most of what ships is slack
    kernel = jax.jit(lambda a, m: unpack_ragged(a, m, s, interpret=True))
    want_c, want_hw = kernel(clean.view(np.uint32), meta)
    got_c, got_hw = kernel(dirty.view(np.uint32), meta)
    ref_c, _ = jax.jit(lambda a, m: unpack_ragged(a, m, s))(dirty, meta)
    assert bool(jnp.array_equal(got_c, want_c)) and bool(jnp.array_equal(got_c, ref_c))
    np.testing.assert_array_equal(np.asarray(got_hw), np.asarray(want_hw))
    assert not np.asarray(got_c[1]).any()


# --------------------------------------------------------- packing identity


@pytest.fixture(scope="module")
def ragged_pair():
    engine = InferenceEngine(_cfg("mobilenet_v2", batch=8))
    batcher = Batcher(engine, max_batch=8, max_delay_ms=5.0)
    batcher.start()
    yield engine, batcher
    batcher.stop()
    engine.close()


def test_packed_equals_solo_through_batcher(ragged_pair):
    """Ragged packing identity: every image packed into shared arenas
    answers exactly what the same image submitted solo (classic padded
    canvas) answers."""
    engine, batcher = ragged_pair
    assert batcher.ragged
    rng = np.random.RandomState(20260804)
    imgs = [
        (rng.rand(rng.randint(12, 96), rng.randint(12, 96), 3) * 255)
        .astype(np.uint8)
        for _ in range(11)
    ]
    futs = []
    for im in imgs:
        h, w = im.shape[:2]
        lease = batcher.lease_ragged(h * w * 3, 96)
        lease.row[:] = im.reshape(-1)
        futs.append(lease.commit((h, w)))
    packed = [f.result(timeout=60) for f in futs]
    for im, got in zip(imgs, packed):
        canvas, hw = _padded([im], 96)
        solo = batcher.submit(canvas[0], tuple(hw[0])).result(timeout=60)
        for a, b in zip(got, solo):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_canvas_commit_matches_row_write(ragged_pair):
    """The PIL-fallback shape — commit(hw, canvas=tight) — lands the same
    bytes as the native decode-into-row shape."""
    _, batcher = ragged_pair
    rng = np.random.RandomState(7)
    im = (rng.rand(33, 47, 3) * 255).astype(np.uint8)
    l1 = batcher.lease_ragged(im.size, 96)
    l1.row[:] = im.reshape(-1)
    r1 = l1.commit((33, 47)).result(timeout=60)
    r2 = batcher.lease_ragged(im.size, 96).commit(
        (33, 47), canvas=im).result(timeout=60)
    for a, b in zip(r1, r2):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_lease_ragged_oversize_raises(ragged_pair):
    _, batcher = ragged_pair
    with pytest.raises(ValueError):
        batcher.lease_ragged(96 * 96 * 3 + 1, 96)


def test_ragged_padding_telemetry(ragged_pair):
    """Shipped-pixel accounting: with small images packed, the engine's
    dispatched-row counter and the batcher's dispatched-pixel counter sit
    strictly below the full-bucket numbers classic padding would ship."""
    engine, batcher = ragged_pair
    rng = np.random.RandomState(3)
    futs = []
    for _ in range(8):
        im = (rng.rand(24, 24, 3) * 255).astype(np.uint8)
        lease = batcher.lease_ragged(im.size, 96)
        lease.row[:] = im.reshape(-1)
        futs.append(lease.commit((24, 24)))
    for f in futs:
        f.result(timeout=60)
    econ = engine.econ_stats()
    cells = [c for rep in econ for c in rep["buckets"] if c["rows"]]
    assert cells
    assert any(c["rows_dispatched"] < c["batch_bucket"] * c["batches"]
               for c in cells), cells
    pad = [p for p in batcher.builder_stats()["padding"].values()
           if p["rows_real"]]
    assert pad
    # Classic padding ships rows_dispatched full canvases; ragged arenas
    # ship strictly fewer pixels than that for small images.
    full = lambda p: p["rows_dispatched"] * p["canvas"] ** 2
    assert any(p["px_dispatched"] < full(p) for p in pad), pad


# ------------------------------------------------------------ config seams


def test_yuv420_wire_forces_classic():
    engine = InferenceEngine(
        _cfg("mobilenet_v2", wire_format="yuv420", canvas=96))
    try:
        assert not engine.ragged
        batcher = Batcher(engine, max_batch=4, max_delay_ms=2.0)
        assert not batcher.ragged
    finally:
        engine.close()


def test_ragged_disables_packed_io():
    engine = InferenceEngine(_cfg("mobilenet_v2", packed_io=True))
    try:
        assert engine.ragged and not engine.cfg.packed_io
    finally:
        engine.close()


# ------------------------------------------------------------- jobs staging


def test_jobs_stage_one_uses_ragged_lease(ragged_pair):
    """Bulk chunks ride the packed-slab path: stage_image(bulk=True) on a
    ragged batcher stages through lease_ragged and the answer matches the
    solo classic submit for the same JPEG."""
    from types import SimpleNamespace

    from PIL import Image

    from tensorflow_web_deploy_tpu.ops.image import decode_image
    from tensorflow_web_deploy_tpu.serving.staging import stage_image

    engine, batcher = ragged_pair
    rng = np.random.RandomState(11)
    buf = io.BytesIO()
    Image.fromarray((rng.rand(40, 56, 3) * 255).astype(np.uint8)).save(
        buf, "JPEG", quality=90)
    data = buf.getvalue()

    mv = SimpleNamespace(name="m", version=1, engine=engine)
    slot = stage_image(data, batcher=batcher, mv=mv, cache=None, topk=3,
                       buckets=engine.cfg.canvas_buckets, span=None,
                       bulk=True)
    assert slot[0] == "own"
    _, future, orig, flight, lease = slot
    assert flight is None and lease is not None
    got = future.result(timeout=60)
    assert orig == (40, 56)

    # Solo reference decoded by the SAME decoder the staged path used
    # (libjpeg when the native extension is up, PIL otherwise) — the
    # parity under test is packing, not libjpeg-vs-PIL IDCT rounding.
    from tensorflow_web_deploy_tpu import native

    img = None
    if native.available() and native.plan_decode_packed(data, (96,)):
        tight = np.zeros(96 * 96 * 3, np.uint8)
        hw = native.decode_packed_into(data, tight, 96)
        if hw is not None:
            img = tight[: hw[0] * hw[1] * 3].reshape(hw[0], hw[1], 3)
    if img is None:
        img = decode_image(data)
    canvas, hw = _padded([img], 96)
    solo = batcher.submit(canvas[0], tuple(hw[0])).result(timeout=60)
    for a, b in zip(got, solo):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
