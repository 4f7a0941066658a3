"""End-to-end native-zoo serving: engine + batcher without TensorFlow.

The ``--model native:<name>`` path (SURVEY.md §7 M1 fallback track) must
flow through the exact same engine machinery as frozen graphs: canvas
preprocessing, bf16 cast, mesh sharding, on-device top-k.
"""

import numpy as np
import pytest

from tensorflow_web_deploy_tpu.serving.batcher import Batcher
from tensorflow_web_deploy_tpu.serving.engine import InferenceEngine
from tensorflow_web_deploy_tpu.utils.config import ModelConfig, ServerConfig


@pytest.fixture(scope="module")
def native_engine():
    cfg = ServerConfig(
        model=ModelConfig(
            name="mobilenet_v2",
            source="native",
            zoo_width=0.25,
            zoo_classes=12,
            input_size=(64, 64),
            preprocess="inception",
            topk=3,
        ),
        canvas_buckets=(96,),
        max_batch=8,
        warmup=False,
    )
    return InferenceEngine(cfg)


def test_native_engine_topk(native_engine, rng):
    n = 8
    canvases = (rng.rand(n, 96, 96, 3) * 255).astype(np.uint8)
    hws = np.full((n, 2), 96, np.int32)
    scores, idx = native_engine.run_batch(canvases, hws)
    assert scores.shape == (n, 3) and idx.shape == (n, 3)
    assert np.all(np.isfinite(scores))
    assert np.all((idx >= 0) & (idx < 12))
    # top-k must be sorted descending
    assert np.all(np.diff(scores, axis=1) <= 1e-6)


def test_native_engine_through_batcher(native_engine, rng):
    batcher = Batcher(native_engine, max_batch=8, max_delay_ms=5.0)
    batcher.start()
    try:
        futures = [
            batcher.submit((rng.rand(96, 96, 3) * 255).astype(np.uint8), (96, 96))
            for _ in range(16)
        ]
        rows = [f.result(timeout=60) for f in futures]
    finally:
        batcher.stop()
    assert len(rows) == 16
    for scores, idx in rows:
        assert scores.shape == (3,) and np.all(np.isfinite(scores))


def test_native_engine_healthcheck(native_engine):
    assert native_engine.healthcheck()


def test_dispatch_oversize_batch_raises(native_engine, rng):
    """A batch above the top bucket must never reach jit with a
    never-compiled shape (request-time compile stall) — it raises instead."""
    top = native_engine.batch_buckets[-1]
    n = top + 1
    canvases = (rng.rand(n, 96, 96, 3) * 255).astype(np.uint8)
    hws = np.full((n, 2), 96, np.int32)
    with pytest.raises(ValueError, match="top batch bucket"):
        native_engine.dispatch_batch(canvases, hws)


def test_run_batch_oversize_chunks(native_engine, rng):
    """run_batch splits oversized batches into top-bucket chunks and the
    result matches per-chunk execution row-for-row."""
    top = native_engine.batch_buckets[-1]
    n = 2 * top + 3
    canvases = (rng.rand(n, 96, 96, 3) * 255).astype(np.uint8)
    hws = np.full((n, 2), 96, np.int32)
    scores, idx = native_engine.run_batch(canvases, hws)
    assert scores.shape[0] == n and idx.shape[0] == n
    s0, i0 = native_engine.run_batch(canvases[:top], hws[:top])
    np.testing.assert_allclose(scores[:top], s0, rtol=1e-5)
    np.testing.assert_array_equal(idx[:top], i0)


def test_native_detect_nondefault_input_size(rng):
    """Anchor grid must follow the configured input size (not the spec
    default) — regression for the adapter/engine size reconciliation."""
    cfg = ServerConfig(
        model=ModelConfig(
            name="ssd_mobilenet",
            source="native",
            task="detect",
            zoo_width=0.25,
            zoo_classes=6,
            input_size=(96, 96),
            preprocess="inception",
        ),
        canvas_buckets=(96,),
        max_batch=8,
        warmup=False,
    )
    engine = InferenceEngine(cfg)
    canvases = (rng.rand(8, 96, 96, 3) * 255).astype(np.uint8)
    hws = np.full((8, 2), 96, np.int32)
    boxes, scores, classes, num = engine.run_batch(canvases, hws)
    assert boxes.shape[0] == 8 and boxes.shape[2] == 4
    assert np.all(num >= 0)


def test_pb_source_requires_path():
    with pytest.raises(ValueError, match="requires pb_path"):
        ModelConfig(name="x", source="pb")


def test_unknown_native_name_is_valueerror():
    from tensorflow_web_deploy_tpu.utils.config import model_config

    with pytest.raises(ValueError, match="native:"):
        model_config("native:resnet_50")


# ---------------------------------------------------------------------------
# yuv420 wire format through the full engine + batcher
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def yuv_engines():
    """Same tiny model served over both wire formats (shared zoo weights:
    native_converted caches by spec, so params match exactly)."""
    def mk(wire):
        return InferenceEngine(
            ServerConfig(
                model=ModelConfig(
                    name="mobilenet_v2",
                    source="native",
                    zoo_width=0.25,
                    zoo_classes=12,
                    input_size=(64, 64),
                    preprocess="inception",
                    topk=3,
                    dtype="float32",  # parity across wires, not bf16 noise
                ),
                canvas_buckets=(96,),
                max_batch=8,
                wire_format=wire,
                warmup=False,
            )
        )

    return mk("rgb"), mk("yuv420")


def test_yuv420_wire_prediction_parity(yuv_engines):
    """Top-1 class and scores must track the rgb wire despite chroma loss.

    Deterministic smooth image: per-pixel random chroma would exaggerate
    4:2:0 loss and (with random-init zoo weights whose scores are nearly
    uniform) let top-1 flip between two near-tied classes.
    """
    rgb_eng, yuv_eng = yuv_engines
    yy, xx = np.mgrid[0:80, 0:72].astype(np.float32)
    img = (
        np.stack([yy * 2, xx * 2, 200 - yy - xx], axis=-1).clip(0, 255).astype(np.uint8)
    )
    out_rgb = rgb_eng.run_batch(*[np.stack([a]) for a in rgb_eng.prepare(img)])
    out_yuv = yuv_eng.run_batch(*[np.stack([a]) for a in yuv_eng.prepare(img)])
    scores_rgb, idx_rgb = out_rgb[0][0], out_rgb[1][0]
    scores_yuv, idx_yuv = out_yuv[0][0], out_yuv[1][0]
    assert idx_rgb[0] == idx_yuv[0]
    np.testing.assert_allclose(scores_rgb, scores_yuv, atol=0.05)


def test_yuv420_wire_through_batcher(yuv_engines, rng):
    _, yuv_eng = yuv_engines
    b = Batcher(yuv_eng, max_batch=4, max_delay_ms=1.0)
    b.start()
    try:
        futs = []
        for _ in range(6):
            img = rng.randint(0, 256, (50, 60, 3)).astype(np.uint8)
            canvas, hw = yuv_eng.prepare(img)
            futs.append(b.submit(canvas, hw))
        for f in futs:
            scores, idx = f.result(timeout=60)
            assert scores.shape == (3,) and idx.shape == (3,)
    finally:
        b.stop()


def test_yuv420_requires_mod4_canvas():
    with pytest.raises(ValueError, match="divisible by 4"):
        ServerConfig(
            model=ModelConfig(name="m", source="native"),
            canvas_buckets=(98,),
            wire_format="yuv420",
        )


def test_unknown_wire_format_rejected():
    with pytest.raises(ValueError, match="wire_format"):
        ServerConfig(model=ModelConfig(name="m", source="native"), wire_format="rgba")


def _mk_engine(packed, task="classify", wire="rgb"):
    if task == "classify":
        mc = ModelConfig(
            name="mobilenet_v2", source="native", zoo_width=0.25, zoo_classes=12,
            input_size=(64, 64), preprocess="inception", dtype="float32", topk=3,
        )
    else:
        mc = ModelConfig(
            name="ssd_mobilenet", source="native", zoo_width=0.25, zoo_classes=10,
            input_size=(96, 96), preprocess="inception", dtype="float32", task="detect",
        )
    cfg = ServerConfig(
        model=mc, canvas_buckets=(96,) if task == "classify" else (128,),
        batch_buckets=(8,), warmup=False, packed_io=packed, wire_format=wire,
    )
    return InferenceEngine(cfg)


@pytest.mark.parametrize("wire", ["rgb", "yuv420"])
@pytest.mark.parametrize("task", ["classify", "detect"])
def test_packed_io_matches_unpacked(rng, task, wire):
    """packed_io=True (one buffer in, one packed f32 array out — 3
    host↔device hops instead of 5) must be bit-compatible with the plain path,
    including the uint16 hw trailer decode for non-square valid regions."""
    s = 96 if task == "classify" else 128
    n = 5
    eng_p = _mk_engine(True, task, wire)
    eng_u = _mk_engine(False, task, wire)
    imgs = (rng.rand(n, s, s, 3) * 255).astype(np.uint8)
    # engine.prepare packs to the wire format (I420 for yuv420)
    canvases = np.stack([eng_p.prepare(i)[0] for i in imgs])
    hws = np.array([[s, s], [50, 70], [33, s], [s, 41], [64, 64]], np.int32)

    packed = eng_p.run_batch(canvases, hws)
    plain = eng_u.run_batch(canvases, hws)
    assert len(packed) == len(plain)
    for a, b in zip(packed, plain):
        assert a.shape == b.shape and a.dtype == b.dtype
        np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-6)
