#!/usr/bin/env python3
"""Decides ``correct``: the plain reference over a sample of the window's answers.

Reads one JSON document on standard input (the model's sizes, the seed, the
limits, and per sampled image its JPEG bytes and the scores the server
answered with), prints one JSON object on standard output. It runs once the
server child has gone, so it may take the chip; it imports nothing of the
program and makes the weights itself from the seed (``reference/weights.py``).

Per image: decode the JPEG (PIL; the traffic never exceeds the largest
canvas, so the server's decoder does no DCT downscale either), resize and
normalise, forward in float32 at ``highest``, softmax. Then, for each of the
``topk`` (class, score) pairs that the server answered,

    e = ln(score served) - ln(reference probability of that class)
    s = standard deviation over the classes of ln(reference probability)

and two of the numbers compared are the root mean square and the maximum of
|e| / s over every pair of the sample (:func:`compare`). ``e`` is the served
logit's error up to the softmax's constant; dividing by the spread of the
image's own logits makes the number mean the same at every seed, whose
weights set that spread. A class the reference thinks improbable, a score
from another image, a dropped normalisation, or fp8 arithmetic read large.

The third, ``int8_weight_share`` (:func:`weight_share`), is for the step
below bfloat16 that those two cannot see: kernels held in int8, arithmetic
still bfloat16, which reads within twice a sound run's error. What such
kernels do to each answer is known exactly: walk the float32 network once
with the kernels as bfloat16 holds them and once as int8 would
(``forward.stored_as``); the difference d is the direction. Most of a served
error is the same for every image of a class, whatever caused it, and says
nothing; what is left once each class's mean over the sample is taken out
of both e and d differs from image to image, and there bfloat16 rounding is
noise that averages out against d while int8 kernels reproduce it. The
number is the least-squares share of d in e: near 0 for bfloat16 kernels,
near 1 for int8 ones.

With ``"control"`` set to one of ``reference/forward.py``'s 8-bit precisions
the served scores are replaced by the top-k of the reference computed in
that precision: the control that the comparison has to call wrong.

This file is the default ``check.child`` (``manifest.py::named``): a
configuration whose network the walkers of ``reference/nets.py`` cannot
express names a child of its own, which reads the same document and prints
the same answer. What any such child needs is importable from here:
:func:`compile_cache`, :func:`pixels`, :func:`in_blocks`, :func:`compare`,
:func:`weight_share` and :func:`answer`.
"""

from __future__ import annotations

import base64
import io
import json
import os
import sys
from pathlib import Path

if __package__ in (None, ""):
    sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

import numpy as np  # noqa: E402

BLOCK = 16  # images per reference forward, so that float32 activations fit beside anything


def compare(ref_probs: np.ndarray, served: list[list[tuple[int, float]]]) -> dict[str, float]:
    """The two numbers, from reference probabilities [N, classes] and the
    served (class, score) pairs of the same N images."""
    logp = np.log(np.maximum(ref_probs.astype(np.float64), 1e-300))
    spread = logp.std(axis=1)
    errs = []
    for n, pairs in enumerate(served):
        for cls, score in pairs:
            if not 0 <= cls < ref_probs.shape[1] or not score > 0:
                errs.append(np.inf)  # no such class, or a score no softmax gives
                continue
            errs.append(abs(np.log(score) - logp[n, cls]) / spread[n])
    errs = np.asarray(errs)
    return {"logit_rms": float(np.sqrt(np.mean(errs ** 2))), "logit_max": float(errs.max())}


def weight_share(logp_stated: np.ndarray, logp_low: np.ndarray, spread: np.ndarray,
                 served: list[list[tuple[int, float]]]) -> float:
    """How much of what lower-precision kernels would do to these answers is
    in them: reference log-probabilities [N, classes] with the kernels as
    the configuration states them and as the tier below holds them, each
    image's logit spread, and the served pairs. Infinite where it cannot be
    told: no class answered for two images, or an answer no softmax gives."""
    e, d, classes = [], [], []
    for n, pairs in enumerate(served):
        for cls, score in pairs:
            if not 0 <= cls < logp_stated.shape[1] or not score > 0:
                return float("inf")
            e.append((np.log(score) - logp_stated[n, cls]) / spread[n])
            d.append((logp_low[n, cls] - logp_stated[n, cls]) / spread[n])
            classes.append(cls)
    e, d, classes = np.asarray(e), np.asarray(d), np.asarray(classes)
    for cls in np.unique(classes):   # what is the same for every image of a class says nothing
        of = classes == cls
        e[of] -= e[of].mean()
        d[of] -= d[of].mean()
    return float(e @ d / (d @ d)) if d @ d > 0 else float("inf")


def compile_cache() -> None:
    """The program's own rule (utils/env.py): the variable if it is set,
    else a fixed directory inside the checkout."""
    import jax

    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir",
                          str(Path(__file__).resolve().parent.parent / ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 1.0)


def pixels(item: dict) -> np.ndarray:
    """An item's JPEG as the server's decoder yields it: RGB, uint8 [h, w, 3]."""
    from PIL import Image

    return np.asarray(Image.open(io.BytesIO(base64.b64decode(item["jpeg"]))).convert("RGB"))


def in_blocks(fn, xs: list[np.ndarray], block: int = BLOCK) -> np.ndarray:
    """``fn`` over equal-shaped ``xs``, ``block`` at a time (the last block
    padded with its last row, so one shape compiles), rows concatenated."""
    out = []
    for i in range(0, len(xs), block):
        part = xs[i:i + block]
        x = np.stack(part + [part[-1]] * (block - len(part)))
        out.append(np.asarray(fn(x))[:len(part)])
    return np.concatenate(out)


def answer(values: dict[str, float], limits: dict[str, float], images: int, platform: str) -> dict:
    """The child's one line: each number the configuration limits beside its
    limit, and ``correct`` only if none is over."""
    compared = {name: {"value": values[name], "limit": limit} for name, limit in limits.items()}
    return {"correct": all(c["value"] <= c["limit"] for c in compared.values()), "compared": compared,
            "images": images, "platform": platform}


def main() -> int:
    doc = json.load(sys.stdin)
    import jax

    compile_cache()
    from benchmark.reference import forward, weights

    m = doc["model"]
    params = weights.make(m["network"], m["input_size"], m["num_classes"], m["width"], doc["seed"])
    xs = [np.asarray(forward.preprocess(pixels(item), m["input_size"])) for item in doc["items"]]

    def probs_of(precision, weights_=params):
        fn = forward.make_probs(m["network"], m["input_size"], m["num_classes"], m["width"], precision)
        weights_ = jax.device_put(weights_)
        return in_blocks(lambda x: fn(weights_, x), xs)

    ref = probs_of("float32")
    logp = lambda p: np.log(np.maximum(p.astype(np.float64), 1e-300))
    stated, below = (logp(probs_of("float32", forward.stored_as(params, tier))) for tier in (m["dtype"], "int8"))
    served = [[(int(c), float(s)) for c, s in item["served"]] for item in doc["items"]]
    if doc.get("control"):
        low = probs_of(doc["control"])
        k = m["topk"]
        top = np.argsort(-low, axis=1)[:, :k]
        served = [[(int(c), float(low[n, c])) for c in top[n]] for n in range(len(low))]
    values = compare(ref, served)
    values["int8_weight_share"] = weight_share(stated, below, logp(ref).std(axis=1), served)
    print(json.dumps(answer(values, doc["limits"], len(xs), jax.devices()[0].platform)))
    return 0


if __name__ == "__main__":
    sys.exit(main())
