#!/usr/bin/env python3
"""Decides ``correct`` for ``reference/longcat.py``'s model: ``check.py``'s
document in, its answer out (``reference/__init__.py`` has the contract).

Every leaf is made here from the seed in float32, a layer at a time, on
threads, while the device walks the layer before: the plain reference at
``highest``, one image at a time, full T x T attention, over a sample of the
window's own answers. An image's answer is ``answer_steps`` top-k lists; the
reference's distribution for step ``s`` is the one after the image's tokens
and the ids that the *served* steps before it put first (one causal forward
over all of them reads every step, ``longcat.py`` says why). Compared are
``logit_rms`` and ``logit_max`` as ``check.compare`` defines them, over
every (image, step, class) that was served, and ``int8_weight_share``
(:func:`int8_share`), for the one control those two cannot see.

With ``control`` set (one of ``longcat.CONTROLS``) the reference computed
that way, greedily, stands in for the served answers, and has to come out
not correct: a lower precision of the dense and expert weights (``int8``,
``fp8``) or a part of the layer left out (``no_held_experts``,
``no_zero_experts``, ``no_shortcut``).
"""

from __future__ import annotations

import json
import os
import sys
from concurrent.futures import ThreadPoolExecutor
from pathlib import Path

import numpy as np

if __package__ in (None, ""):
    sys.path.insert(0, str(Path(__file__).resolve().parents[2]))

from benchmark import check  # noqa: E402
from benchmark.reference import longcat  # noqa: E402


class Weights:
    """The model's leaves from the seed: the outer ones at once, a layer's
    on demand (5 GB in float32 at the published widths), the next layer's
    being made on threads meanwhile. ``keep`` holds every layer once made
    (a control walks five times)."""

    def __init__(self, m: dict, seed: int, keep: bool):
        self.m, self.seed, self.keep = m, seed, keep
        self.pool = ThreadPoolExecutor(os.cpu_count() or 4)
        self.made: dict[int, dict] = {}
        self.outer = self._wait(self._start("", longcat.outer_leaves(m)))
        self.pending = {0: self._start_layer(0)}

    def _start(self, prefix: str, shapes: dict):
        """(names, futures) of the leaves ``prefix + name``, the largest first."""
        names = sorted(shapes, key=lambda n: -int(np.prod(shapes[n])))
        return names, [self.pool.submit(longcat.make_leaf, self.seed, prefix + n, shapes[n], self.m) for n in names]

    def _start_layer(self, l: int):
        return self._start(f"layer{l}/", longcat.layer_leaves(self.m))

    @staticmethod
    def _wait(started) -> dict:
        names, futures = started
        return {n: f.result() for n, f in zip(names, futures)}

    def layer(self, l: int) -> dict:
        if l in self.made:
            return self.made[l]
        started = self.pending.pop(l, None) or self._start_layer(l)
        if l + 1 < self.m["num_layers"] and l + 1 not in self.made:
            self.pending[l + 1] = self._start_layer(l + 1)
        out = self._wait(started)
        if self.keep:
            self.made[l] = out
        return out


def walk(m: dict, weights: Weights, tokens: list[np.ndarray], ids: list[list[int]], steps: int,
         controls: tuple = (None,)) -> dict:
    """For each of ``controls`` (None: the reference itself), per image the
    distributions of its last ``steps`` positions: layer by layer over all
    the images and all the controls, so that a layer's weights are made and
    sent to the device once a walk."""
    import jax

    outer = jax.device_put({k: weights.outer[k] for k in ("embed/patch", "embed/token")})
    xs = {c: [longcat.embed(outer, t, i) for t, i in zip(tokens, ids)] for c in controls}
    layer = {c: jax.jit(lambda w, x, c=c: longcat.double_layer(m, w, x, c)) for c in controls}
    for l in range(m["num_layers"]):
        w = jax.device_put(weights.layer(l))
        xs = {c: [layer[c](w, x) for x in xs[c]] for c in controls}
        jax.block_until_ready(list(xs.values()))
        del w
    head = jax.device_put({k: weights.outer[k] for k in ("final_norm", "head")})
    return {c: [np.asarray(longcat.head_probs(m, head, x[-steps:])) for x in xs[c]] for c in controls}


def greedy(m: dict, weights: Weights, tokens: list[np.ndarray], control: str) -> list[list]:
    """What a server computing as ``control`` says would answer: a walk a step."""
    answers: list[list] = [[] for _ in tokens]
    for _ in range(m["answer_steps"]):
        dists = walk(m, weights, tokens, [[step[0][0] for step in a] for a in answers], 1, (control,))[control]
        for a, d in zip(answers, dists):
            a.append([[int(c), float(d[0][c])] for c in np.argsort(-d[0])[:m["topk"]]])
    return answers


def int8_share(ref: np.ndarray, low: np.ndarray, pairs: list[list[tuple[int, float]]]) -> float:
    """How much of what int8 weights do to these answers is in them: the
    least-squares share, in the served error e = ln(score) - ln(reference),
    of d = ln(reference with the dense and expert weights held in int8) -
    ln(reference), each over the spread of its distribution's logits. int8
    weights read within a few times a sound run's ``logit_rms`` and cannot
    be told by it; but what they do to each answer is known exactly, and
    bfloat16's rounding is noise that averages out against it: near 0 for
    the stated weights, near 1 for int8 ones. (``check.weight_share`` takes
    each class's mean over the sample out first; here an id is hardly ever
    answered twice, so nothing would be left.) Infinite where it cannot be
    told."""
    logp = lambda p: np.log(np.maximum(p.astype(np.float64), 1e-300))
    lr, ll = logp(ref), logp(low)
    spread = lr.std(axis=1)
    e, d = [], []
    for n, row in enumerate(pairs):
        for cls, score in row:
            if not 0 <= cls < ref.shape[1] or not score > 0:
                return float("inf")
            e.append((np.log(score) - lr[n, cls]) / spread[n])
            d.append((ll[n, cls] - lr[n, cls]) / spread[n])
    e, d = np.asarray(e), np.asarray(d)
    return float(e @ d / (d @ d)) if d @ d > 0 else float("inf")


def main() -> int:
    doc = json.load(sys.stdin)
    import jax

    check.compile_cache()
    m, control = doc["model"], doc.get("control")
    if control and control not in longcat.CONTROLS:
        raise ValueError(f"unknown control {control!r}: one of {longcat.CONTROLS}")
    weights = Weights(m, doc["seed"], keep=bool(control))
    tokens = [longcat.patches(check.pixels(item), m["patch"]) for item in doc["items"]]
    served = [item["served"] for item in doc["items"]]
    if control:
        served = greedy(m, weights, tokens, control)
    steps = m["answer_steps"]
    prior = [[int(step[0][0]) for step in a[:steps - 1]] for a in served]
    walked = walk(m, weights, tokens, prior, steps, (None, "int8"))
    ref, low = np.concatenate(walked[None]), np.concatenate(walked["int8"])
    pairs = [[(int(c), float(v)) for c, v in step] for a in served for step in a]
    values = check.compare(ref, pairs)
    values["int8_weight_share"] = int8_share(ref, low, pairs)
    print(json.dumps(check.answer(values, doc["limits"], len(tokens), jax.devices()[0].platform)))
    return 0


if __name__ == "__main__":
    sys.exit(main())
