"""Fused Pallas preprocess kernel vs the XLA reference path.

Runs in interpret mode on the CPU backend — same kernel code that Mosaic
compiles on TPU (SURVEY.md §4: no-hardware test strategy).
"""

import numpy as np
import pytest

from tensorflow_web_deploy_tpu.ops.image import make_preprocess_fn, rgb_to_yuv420_canvas
from tensorflow_web_deploy_tpu.ops.pallas_preprocess import preprocess_i420


def _pack(rng, b, s):
    canv = rng.randint(0, 256, (b, s, s, 3)).astype(np.uint8)
    return np.stack([rgb_to_yuv420_canvas(c) for c in canv])


@pytest.mark.parametrize("mode", ["inception", "zero_one", "raw"])
def test_pallas_matches_xla_yuv_path(rng, mode):
    import jax

    packed = _pack(rng, 3, 64)
    hws = np.array([[64, 64], [48, 60], [33, 41]], np.int32)
    ref = np.asarray(
        jax.jit(make_preprocess_fn(32, 32, mode, wire="yuv420", resize="matmul"))(
            packed, hws
        )
    )
    got = np.asarray(preprocess_i420(packed, hws, 32, 32, mode, interpret=True))
    # Kernel and matmul path share the plane-wise structure (resize planes,
    # convert + clip after); only dot-product accumulation order differs.
    atol = {"raw": 1e-3, "zero_one": 1e-5, "inception": 1e-5}[mode]
    np.testing.assert_allclose(got, ref, atol=atol)


def test_pallas_row_tiled_canvas_matches_xla(rng):
    """A canvas that is a multiple of 128 streams through the kernel in
    row tiles (two of 512 rows at 1024), each with its own U and V blocks;
    the accumulated planes must equal the one-pass XLA resize."""
    import jax

    from tensorflow_web_deploy_tpu.ops.pallas_preprocess import row_tile

    s = 1024
    assert row_tile(s) == 512
    packed = _pack(rng, 2, s)
    hws = np.array([[s, s], [700, 333]], np.int32)  # second: tile 1 half empty
    ref = np.asarray(
        jax.jit(make_preprocess_fn(32, 32, "inception", wire="yuv420",
                                   resize="matmul"))(packed, hws)
    )
    got = np.asarray(preprocess_i420(packed, hws, 32, 32, "inception",
                                     interpret=True))
    np.testing.assert_allclose(got, ref, atol=1e-5)


def test_pallas_rejects_canvas_that_cannot_fit_vmem():
    from tensorflow_web_deploy_tpu.ops.pallas_preprocess import row_tile
    from tensorflow_web_deploy_tpu.utils.config import ModelConfig, ServerConfig

    assert row_tile(96) == 96 and row_tile(2048) == 512
    with pytest.raises(ValueError, match="multiple of 128"):
        row_tile(1100)
    with pytest.raises(ValueError, match="multiples of 128"):
        ServerConfig(model=ModelConfig(name="m", source="native"),
                     wire_format="yuv420", resize="pallas",
                     canvas_buckets=(512, 1100))


def test_pallas_rejects_bad_shapes_and_modes(rng):
    packed = _pack(rng, 1, 64)
    hws = np.array([[64, 64]], np.int32)
    with pytest.raises(ValueError, match="I420"):
        preprocess_i420(np.zeros((1, 64, 64), np.uint8), hws, 32, 32, interpret=True)
    with pytest.raises(ValueError, match="normalize"):
        preprocess_i420(packed, hws, 32, 32, "caffe", interpret=True)


def test_gather_and_matmul_resize_identical(rng):
    """The two XLA resize paths share coordinates and taps exactly."""
    import jax

    canv = rng.randint(0, 256, (2, 48, 48, 3)).astype(np.uint8)
    hws = np.array([[48, 48], [31, 47]], np.int32)
    g = np.asarray(jax.jit(make_preprocess_fn(24, 24, "inception", resize="gather"))(canv, hws))
    m = np.asarray(jax.jit(make_preprocess_fn(24, 24, "inception", resize="matmul"))(canv, hws))
    np.testing.assert_allclose(g, m, atol=1e-5)


def test_engine_with_pallas_resize(rng):
    """Full engine e2e with the fused kernel (interpret on CPU)."""
    from tensorflow_web_deploy_tpu.serving.engine import InferenceEngine
    from tensorflow_web_deploy_tpu.utils.config import ModelConfig, ServerConfig

    def mk(resize):
        return InferenceEngine(
            ServerConfig(
                model=ModelConfig(
                    name="mobilenet_v2",
                    source="native",
                    zoo_width=0.25,
                    zoo_classes=9,
                    input_size=(64, 64),
                    preprocess="inception",
                    topk=3,
                    dtype="float32",
                ),
                canvas_buckets=(96,),
                max_batch=4,
                wire_format="yuv420",
                resize=resize,
                warmup=False,
            )
        )

    yy, xx = np.mgrid[0:80, 0:72].astype(np.float32)
    img = np.stack([yy * 2, xx * 2, 200 - yy - xx], -1).clip(0, 255).astype(np.uint8)
    eng_p, eng_m = mk("pallas"), mk("matmul")
    out_p = eng_p.run_batch(*[np.stack([a]) for a in eng_p.prepare(img)])
    out_m = eng_m.run_batch(*[np.stack([a]) for a in eng_m.prepare(img)])
    assert out_p[1][0][0] == out_m[1][0][0]  # same top-1
    np.testing.assert_allclose(out_p[0], out_m[0], atol=1e-4)


def test_pallas_resize_requires_yuv_wire():
    from tensorflow_web_deploy_tpu.utils.config import ModelConfig, ServerConfig

    with pytest.raises(ValueError, match="yuv420"):
        ServerConfig(
            model=ModelConfig(name="m", source="native"),
            wire_format="rgb",
            resize="pallas",
        )
