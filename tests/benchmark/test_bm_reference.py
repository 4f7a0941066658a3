"""The plain reference, its weights, the comparison and its control, at a size a test holds."""

import base64
import json
import os
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

from benchmark import check, traffic
from benchmark.reference import forward, nets, weights

ROOT = Path(__file__).resolve().parents[2]
TINY = {"network": "mobilenet_v2", "input_size": 64, "num_classes": 16, "width": 0.25,
        "dtype": "bfloat16", "preprocess": "inception", "topk": 5}


def test_weights_are_a_function_of_the_seed_and_leave_batch_norm_off_the_identity():
    a = weights.make("mobilenet_v2", 64, 16, 0.25, 2**31 + 7)
    b = weights.make("mobilenet_v2", 64, 16, 0.25, 2**31 + 7)
    c = weights.make("mobilenet_v2", 64, 16, 0.25, 8)
    assert a.keys() == c.keys() and all(np.array_equal(a[k], b[k]) for k in a)
    assert not np.array_equal(a["params/stem/conv/kernel"], c["params/stem/conv/kernel"])
    assert a["params/stem/conv/kernel"].dtype == np.float32
    assert np.abs(a["batch_stats/stem/bn/mean"]).max() > 0.01
    assert a["batch_stats/stem/bn/var"].min() >= 0.8 and a["params/stem/bn/scale"].max() <= 1.25
    tree = weights.nest(a)
    assert set(tree) == {"params", "batch_stats"}


@pytest.mark.parametrize("arch,size,width", [("mobilenet_v2", 64, 0.25), ("inception_v3", 96, 0.25)])
def test_the_reference_is_the_programs_model_in_float32(arch, size, width):
    """Same weights, same input: the reference's probabilities equal the
    program's flax model's at float32, so a gap on the chip is the served
    path's, not a second opinion about the architecture."""
    import jax
    import jax.numpy as jnp
    from tensorflow_web_deploy_tpu.models.adapter import native_converted

    w = weights.make(arch, size, 16, width, 3)
    cm = native_converted(arch, num_classes=16, width=width, input_size=size)
    assert set(cm.params) == set(w)
    assert all(cm.params[k].shape == w[k].shape for k in w)
    x = np.random.RandomState(0).uniform(-1, 1, (2, size, size, 3)).astype(np.float32)
    with jax.default_matmul_precision("highest"):
        theirs = np.asarray(jax.jit(cm.fn)({k: jnp.asarray(v) for k, v in w.items()}, x)[0])
    ours = np.asarray(forward.make_probs(arch, size, 16, width)(w, x))
    assert np.abs(ours - theirs).max() < 2e-5
    assert ours.sum(-1) == pytest.approx(1.0, abs=1e-5)


def test_the_reference_resizes_and_normalises_as_the_served_path_does():
    from tensorflow_web_deploy_tpu.ops import image as pimg
    px = np.random.RandomState(1).randint(0, 256, (90, 120, 3), np.uint8)
    canvas = np.zeros((128, 128, 3), np.uint8)
    canvas[:90, :120] = px
    theirs = np.asarray(pimg.preprocess_batch(canvas[None], np.asarray([[90, 120]], np.int32), 64, 64, "inception"))[0]
    ours = np.asarray(forward.preprocess(px, 64))
    assert np.abs(ours - theirs).max() < 1e-5
    assert ours.min() >= -1.0 and ours.max() <= 1.0


def test_compare_reads_zero_for_the_reference_itself_and_large_for_wrong_answers():
    rs = np.random.RandomState(0)
    logits = rs.normal(0, 2, (6, 16))
    p = np.exp(logits) / np.exp(logits).sum(-1, keepdims=True)
    top = np.argsort(-p, axis=1)[:, :5]
    served = [[(int(c), float(p[n, c])) for c in top[n]] for n in range(6)]
    exact = check.compare(p, served)
    assert exact["logit_rms"] < 1e-9 and exact["logit_max"] < 1e-9
    shifted = [[(c, s * np.exp(0.2)) for c, s in row] for row in served]     # 0.2 nat on every logit
    spread = np.log(p).std(axis=1)
    assert check.compare(p, shifted)["logit_max"] == pytest.approx(0.2 / spread.min(), rel=1e-6)
    other_image = served[1:] + served[:1]                                      # answers of the wrong image
    assert check.compare(p, other_image)["logit_rms"] > 0.3
    assert check.compare(p, [[(99, 0.5)] * 5] * 6)["logit_max"] == np.inf    # no such class
    assert check.compare(p, [[(1, 0.0)] * 5] * 6)["logit_max"] == np.inf     # a score no softmax gives


def _doc(control, limits):
    mix_path = ROOT / "tests" / "benchmark" / "data" / "tiny-photos.json"
    corpus = traffic.Corpus(traffic.Mix.load(mix_path), 5, threads=2)
    src = traffic.Source(corpus, 5)
    items = []
    for _ in range(12):
        base, k = src.take().images[0]
        items.append({"jpeg": base64.b64encode(traffic.variant(base, k)).decode(),
                      "served": [[0, 0.2]] * 5})
    return {"model": TINY, "seed": 5, "limits": limits, "items": items, "control": control}


def test_weight_share_reads_the_direction_in_the_image_dependent_part_only():
    rs = np.random.RandomState(3)
    stated = rs.normal(-3, 1, (40, 16))
    d = rs.normal(0, 0.05, (40, 16))
    noise = rs.normal(0, 0.02, (40, 16))
    spread = np.ones(40)
    pairs = lambda logp: [[(c, float(np.exp(logp[n, c]))) for c in (1, 4, 7)] for n in range(40)]
    assert check.weight_share(stated, stated + d, spread, pairs(stated + noise)) == pytest.approx(0.0, abs=0.15)
    assert check.weight_share(stated, stated + d, spread, pairs(stated + d + noise)) == pytest.approx(1.0, abs=0.15)
    # an error that is the same for every image of a class says nothing, whichever side has it
    offset = rs.normal(0, 0.5, 16)
    assert check.weight_share(stated, stated + d, spread, pairs(stated + d + offset)) == pytest.approx(1.0, abs=1e-9)
    assert check.weight_share(stated, stated + d + offset, spread, pairs(stated + noise)) == pytest.approx(0.0, abs=0.15)
    # it cannot be told: no class answered twice, or an answer no softmax gives
    once = [[(n % 16, 0.1)] for n in range(16)]
    assert check.weight_share(stated[:16], (stated + d)[:16], spread[:16], once) == np.inf
    assert check.weight_share(stated, stated + d, spread, [[(99, 0.5)]] * 40) == np.inf


def test_stored_as_is_weight_only_quantisation_per_output_channel():
    k = np.random.RandomState(0).normal(0, 0.1, (3, 3, 8, 4)).astype(np.float32)
    params = {"params/a/conv/kernel": k, "params/a/bn/scale": np.full(4, 1.2345678, np.float32)}
    as_int8 = forward.stored_as(params, "int8")
    assert as_int8["params/a/bn/scale"] is params["params/a/bn/scale"]
    q = as_int8["params/a/conv/kernel"]
    amax = np.abs(k).max(axis=(0, 1, 2))
    assert np.abs(q - k).max() <= (amax / 127).max() * 0.51 + np.abs(k).max() * 2.0 ** -7
    for c in range(4):   # at most 255 levels per output channel
        assert len(np.unique(q[..., c])) <= 255
    b = forward.stored_as(params, "bfloat16")["params/a/conv/kernel"]
    assert 0 < np.abs(b - k).max() <= np.abs(k).max() * 2.0 ** -8
    assert forward.stored_as(params, "float32")["params/a/conv/kernel"] is not None
    with pytest.raises(ValueError):
        forward.stored_as(params, "int4")


@pytest.mark.parametrize("control", ["int8_weights", "int8", "fp8", "fp8_e5m2"])
def test_the_control_fails_the_comparison(control):
    """check.py end to end, as run.py calls it: the reference computed in
    8 bits stands in for the program and must come out not correct, under
    limits that the float32 reference itself passes with nothing to spare.
    Kernels in int8 with the arithmetic left in bfloat16, the nearest step
    below what the configurations state, fail on ``int8_weight_share`` alone."""
    doc = _doc(control, {"logit_rms": 0.02 if control != "int8_weights" else 0.05,
                         "logit_max": 0.1 if control != "int8_weights" else 0.2, "int8_weight_share": 0.4})
    proc = subprocess.run([sys.executable, str(ROOT / "benchmark" / "check.py")],
                          input=json.dumps(doc).encode(), capture_output=True, timeout=300,
                          env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert proc.returncode == 0, proc.stderr.decode()[-2000:]
    out = json.loads(proc.stdout.decode().strip().splitlines()[-1])
    assert out["correct"] is False and out["images"] == 12
    if control == "int8_weights":
        assert out["compared"]["int8_weight_share"]["value"] > 0.6
    else:
        assert out["compared"]["logit_rms"]["value"] > 0.02
    assert list(out["compared"]) == ["logit_rms", "logit_max", "int8_weight_share"]


def test_a_control_precision_that_does_not_exist_is_refused():
    with pytest.raises(ValueError):
        forward.JnpOps({}, "int4")


def test_a_network_is_found_by_the_file_and_function_a_configuration_names(tmp_path):
    """A bare name is a function of nets.py; ``file::function`` is any file,
    so a new architecture is a new file: walked for shapes and multiply-adds,
    filled from the seed, and run forward, with nothing edited."""
    assert nets.load("mobilenet_v2") is nets.load("benchmark/reference/nets.py::mobilenet_v2") is nets.mobilenet_v2
    for missing in ("resnet_50", "benchmark/reference/nets.py::resnet_50", "benchmark/reference/nets.py::MV2_BLOCKS"):
        with pytest.raises(ValueError):
            nets.load(missing)
    (tmp_path / "two_layers.py").write_text(
        "def two_layers(o, x, num_classes=1000, width=1.0):\n"
        "    x = o.conv_bn('stem', x, int(8 * width), (3, 3), 2, 'SAME', 'relu6')\n"
        "    x = o.dw_bn('dw', x, 1, 'relu6')\n"
        "    return o.head('logits', x, num_classes)\n")
    network = f"{tmp_path / 'two_layers.py'}::two_layers"
    walked = nets.walk(network, 32, 10, 2.0)
    assert walked.params["params/stem/conv/kernel"] == (3, 3, 3, 16) and walked.params["params/logits/kernel"] == (16, 10)
    assert sum(walked.macs.values()) == 16 * 16 * 27 * 16 + 16 * 16 * 9 * 16 + 16 * 10
    from benchmark.reference import conv_floors
    assert conv_floors.model_macs(network, 32, 10, 2.0) == sum(walked.macs.values())
    w = weights.make(network, 32, 10, 2.0, 2**31 + 1)
    assert set(w) == set(walked.params)
    x = np.random.RandomState(0).uniform(-1, 1, (3, 32, 32, 3)).astype(np.float32)
    probs = np.asarray(forward.make_probs(network, 32, 10, 2.0)(w, x))
    assert probs.shape == (3, 10) and probs.sum(-1) == pytest.approx(1.0, abs=1e-5)
    low = np.asarray(forward.make_probs(network, 32, 10, 2.0, "fp8_e5m2")(w, x))
    assert np.abs(low - probs).max() > 0
