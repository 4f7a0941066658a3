#!/usr/bin/env python3
"""Decides ``correct`` for ``reference/nemotron_h.py``'s model: ``check.py``'s
document in, its answer out (``reference/__init__.py`` has the contract).

Every leaf is made here from the seed in float32, a layer at a time, on
threads (a large leaf in its row blocks), while the device walks the layer
before: the plain reference at ``highest``, one image at a time, the
recurrence token by token, the full T x T softmax and the experts' dense
sum, over a sample of the window's own answers. An image's answer is
``answer_steps`` top-k lists; the reference's distribution for step ``s`` is
the one after the image's tokens and the ids that the *served* steps before
it put first: **one full forward** over all of them reads every step
(everything is causal), so the served prefill and its fifteen steps through
the carried states are held against one pass that has neither. Of the token
embedding only the row blocks that hold those ids are made. Compared are
``logit_rms`` and ``logit_max`` as ``check.compare`` defines them, over every
(image, step, class) that was served, and ``int8_weight_share``
(``longcat_check.int8_share``), for the one control those two cannot see.

With ``control`` set (one of ``nemotron_h.CONTROLS``) the reference computed
that way, greedily, stands in for the served answers, and has to come out
not correct. A greedy step is a whole walk (an expert layer's 2.6 GB of
float32 go to the device again each time), so a control answers the first
``CONTROL_STEPS`` steps of the first ``CONTROL_IMAGES`` items and those are
what is compared: step 1 is the prefill's own, steps 2-4 go through what the
prefill hands on, which is where the two hand-over controls differ. A step's
walk is over the image's tokens and the ids so far, those not yet answered
filled with id 0: what comes later moves nothing before it.
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
from benchmark.reference import nemotron_h  # noqa: E402
from benchmark.reference.longcat_check import int8_share  # noqa: E402

CONTROL_IMAGES, CONTROL_STEPS = 3, 4      # what a control answers and is judged on


class Weights:
    """The model's leaves from the seed: the outer ones at once (of the
    token embedding only rows, on demand), a layer's on demand (an expert
    layer is 2.6 GB in float32 at the published widths), the next layer's
    being made on threads meanwhile. ``keep`` holds every layer once made
    (a control walks seventeen times)."""

    def __init__(self, m: dict, seed: int, keep: bool):
        self.m, self.seed, self.keep = m, seed, keep
        self.kinds = m["hybrid_override_pattern"]
        self.pool = ThreadPoolExecutor(os.cpu_count() or 4)
        self.made: dict[int, dict] = {}
        self.rows: dict[int, np.ndarray] = {}          # the token embedding's rows made so far, by id
        self.on_device: dict = {}                      # what every walk reads and the device keeps: patch embedding, head
        self.jitted: dict = {}                         # a (kind, control)'s layer function, traced once a shape
        outer = {k: v for k, v in nemotron_h.outer_leaves(m).items() if k != "embed/token"}
        self.outer = self._wait(self._start("", outer))
        self.pending = {0: self._start_layer(0)}

    def _start(self, prefix: str, shapes: dict):
        """(name, [a future a block]) of the leaves ``prefix + name``."""
        make = lambda n, b: self.pool.submit(nemotron_h.make_block, self.seed, prefix + n, shapes[n], self.m, b)
        return [(n, [make(n, b) for b in range(len(nemotron_h.blocks(shapes[n])))]) for n in shapes]

    def _start_layer(self, l: int):
        return self._start(f"layer{l}/", nemotron_h.layer_leaves(self.m, self.kinds[l]))

    @staticmethod
    def _wait(started) -> dict:
        return {n: (np.concatenate([f.result() for f in fs]) if len(fs) > 1 else fs[0].result()) for n, fs in started}

    def layer(self, l: int) -> dict:
        if l in self.made:
            return self.made[l]
        started = self.pending.pop(l, None) or self._start_layer(l)
        if l + 1 < len(self.kinds) and l + 1 not in self.made:
            self.pending[l + 1] = self._start_layer(l + 1)
        out = self._wait(started)
        if self.kinds[l] == "E":
            out = nemotron_h.stack_experts(self.m, out)
        if self.keep:
            self.made[l] = out
        return out

    def token_rows(self, ids: list[int]) -> np.ndarray:
        """[len(ids), D]: the ids' rows of the token embedding. A row block
        (64 MB) is made for the ids in it that are not yet known, and dropped."""
        shape = nemotron_h.outer_leaves(self.m)["embed/token"]
        per = nemotron_h.blocks(shape)[0][1]
        new = sorted(set(ids) - set(self.rows))

        def rows_of(block: int) -> dict:
            values = nemotron_h.make_block(self.seed, "embed/token", shape, self.m, block)
            return {i: values[i % per].copy() for i in new if i // per == block}

        for made in self.pool.map(rows_of, sorted({i // per for i in new})):
            self.rows |= made
        return np.stack([self.rows[i] for i in ids]) if ids else np.zeros((0, shape[1]), np.float32)

    def device(self, *names: str) -> dict:
        """The outer leaves ``names`` on the device, sent once a child."""
        import jax

        for n in names:
            if n not in self.on_device:
                self.on_device[n] = jax.device_put(self.outer[n])
        return {n: self.on_device[n] for n in names}

    def layer_fn(self, kind: str, control):
        import jax

        if (kind, control) not in self.jitted:
            self.jitted[kind, control] = jax.jit(
                lambda w, x, n: nemotron_h.layer(self.m, kind, w, x, control, n), static_argnums=(2,))
        return self.jitted[kind, control]


def walk(m: dict, weights: Weights, tokens: list[np.ndarray], ids: list[list[int]], read: list[slice],
         controls: tuple = (None,)) -> dict:
    """For each of ``controls`` (None: the reference itself), per image the
    distributions of its positions ``read``: layer by layer over all the
    images and all the controls, so that a layer's weights are made and sent
    to the device once a walk."""
    import jax

    patch = weights.device("embed/patch")["embed/patch"]
    embedded = [nemotron_h.embed(patch, t, weights.token_rows(i)) for t, i in zip(tokens, ids)]
    xs = {c: list(embedded) for c in controls}
    for l, kind in enumerate(weights.kinds):
        w = jax.device_put(weights.layer(l))
        xs = {c: [weights.layer_fn(kind, c)(w, x, len(t)) for x, t in zip(xs[c], tokens)] for c in controls}
        jax.block_until_ready(list(xs.values()))
        del w
    head = weights.device("final_norm", "head")
    return {c: [np.asarray(nemotron_h.head_probs(m, head, x[r])) for x, r in zip(xs[c], read)] for c in controls}


def greedy(m: dict, weights: Weights, tokens: list[np.ndarray], control: str, steps: int) -> list[list]:
    """What a server computing as ``control`` says would answer in its first
    ``steps`` steps: a walk a step."""
    answers: list[list] = [[] for _ in tokens]
    n_ids = steps - 1
    for s in range(steps):
        ids = [[step[0][0] for step in a] + [0] * (n_ids - len(a)) for a in answers]
        read = [slice(len(t) - 1 + s, len(t) + s) for t in tokens]
        dists = walk(m, weights, tokens, ids, read, (control,))[control]
        for a, d in zip(answers, dists):
            a.append([[int(c), float(d[0][c])] for c in np.argsort(-d[0])[:m["topk"]]])
    return answers


def main() -> int:
    doc = json.load(sys.stdin)
    import jax

    check.compile_cache()
    m, control = doc["model"], doc.get("control")
    if control and control not in nemotron_h.CONTROLS:
        raise ValueError(f"unknown control {control!r}: one of {nemotron_h.CONTROLS}")
    weights = Weights(m, doc["seed"], keep=bool(control))
    items = doc["items"][:CONTROL_IMAGES] if control else doc["items"]
    steps = min(CONTROL_STEPS, m["answer_steps"]) if control else m["answer_steps"]
    tokens = [nemotron_h.patches(check.pixels(item), m["patch"]) for item in items]
    served = [item["served"] for item in items]
    if control:
        served = greedy(m, weights, tokens, control, steps)
    prior = [[int(step[0][0]) for step in a[:steps - 1]] for a in served]
    walked = walk(m, weights, tokens, prior, [slice(len(t) - 1, len(t) - 1 + steps) for t in tokens], (None, "int8"))
    ref, low = np.concatenate(walked[None]), np.concatenate(walked["int8"])
    pairs = [[(int(c), float(v)) for c, v in step] for a in served for step in a]
    values = check.compare(ref, pairs)
    values["int8_weight_share"] = int8_share(ref, low, pairs)
    print(json.dumps(check.answer(values, doc["limits"], len(tokens), jax.devices()[0].platform)))
    return 0


if __name__ == "__main__":
    sys.exit(main())
