#!/usr/bin/env python3
"""Decides ``correct`` for ``reference/brumby.py``'s model: ``check.py``'s
document in, its answer out (``reference/__init__.py`` has the contract).

Every leaf is made here from the seed in float32, a layer at a time, on
threads (a large leaf in its row blocks), while the device walks the layer
before: the plain reference at ``highest``, one image at a time, power
retention in its attention form (no feature map, no state), over a sample of
the window's own answers. An image's answer is ``answer_steps`` top-k lists;
the reference's distribution for step ``s`` is the one after the image's
tokens and the ids that the *served* steps before it put first: **one full
forward** over all of them reads every step (everything is causal), so the
served prefill and its 63 steps through the carried states are held against
one pass that has neither. Of the token embedding only the row blocks that
hold those ids are made. Compared are ``logit_rms`` and ``logit_max`` as
``check.compare`` defines them, over every (image, step, class) that was
served, and ``int8_weight_share`` (``longcat_check.int8_share``), for the
one control those two cannot see.

With ``control`` set (one of ``brumby.CONTROLS``) the reference computed that
way, greedily, stands in for the served answers, and has to come out not
correct: every item's ``answer_steps`` steps, in the recurrent form
(``brumby.recurrent``: the images' tokens in chunks, then one token a row
through the states), every layer on the device at once for the steps.
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
from benchmark.reference import brumby  # noqa: E402
from benchmark.reference.longcat_check import int8_share  # noqa: E402
from benchmark.reference.nemotron_h import blocks  # noqa: E402

class Weights:
    """The model's leaves from the seed: the outer ones at once (of the
    token embedding only row blocks, on demand), a layer's on demand (1.3 GB
    in float32 at the published widths), the next layer's being made on
    threads meanwhile. ``keep`` holds every layer once made (a control reads
    each three times)."""

    def __init__(self, m: dict, seed: int, keep: bool):
        self.m, self.seed, self.keep = m, seed, keep
        self.pool = ThreadPoolExecutor(os.cpu_count() or 4)
        self.made: dict[int, dict] = {}
        self.row_blocks: dict[int, np.ndarray] = {}    # the token embedding's row blocks made so far
        self.on_device: dict = {}                      # what every walk reads and the device keeps
        self.jitted: dict = {}                         # a control's layer function, traced once a shape
        self.int8_of: tuple = (None, None)             # (a layer's leaves on the device, the same in int8)
        outer = {k: v for k, v in brumby.outer_leaves(m).items() if k != "embed/token"}
        self.outer = self._wait(self._start("", outer))
        self.pending = {0: self._start_layer(0)}

    def _start(self, prefix: str, shapes: dict):
        """(name, [a future a block]) of the leaves ``prefix + name``."""
        make = lambda n, b: self.pool.submit(brumby.make_block, self.seed, prefix + n, shapes[n], self.m, b)
        return [(n, [make(n, b) for b in range(len(blocks(shapes[n])))]) for n in shapes]

    def _start_layer(self, l: int):
        return self._start(f"layer{l}/", brumby.layer_leaves(self.m))

    @staticmethod
    def _wait(started) -> dict:
        return {n: (np.concatenate([f.result() for f in fs]) if len(fs) > 1 else fs[0].result()) for n, fs in started}

    def layer(self, l: int) -> dict:
        if l in self.made:
            return self.made[l]
        started = self.pending.pop(l, None) or self._start_layer(l)
        if l + 1 < self.m["num_hidden_layers"] and l + 1 not in self.made:
            self.pending[l + 1] = self._start_layer(l + 1)
        out = self._wait(started)
        if self.keep:
            self.made[l] = out
        return out

    def token_rows(self, ids: list[int]) -> np.ndarray:
        """[len(ids), D]: the ids' rows of the token embedding, from its row
        blocks (64 MB each), each made once, on threads."""
        shape = brumby.outer_leaves(self.m)["embed/token"]
        per = blocks(shape)[0][1]
        new = sorted({i // per for i in ids} - set(self.row_blocks))
        made = self.pool.map(lambda b: brumby.make_block(self.seed, "embed/token", shape, self.m, b), new)
        self.row_blocks |= dict(zip(new, made))
        return np.stack([self.row_blocks[i // per][i % per] for i in ids]) if ids else np.zeros((0, shape[1]), np.float32)

    def device(self, *names: str) -> dict:
        """The outer leaves ``names`` on the device, sent once a child."""
        import jax

        for n in names:
            if n not in self.on_device:
                self.on_device[n] = jax.device_put(self.outer[n])
        return {n: self.on_device[n] for n in names}

    def layer_fn(self, mode):
        """The reference's layer as ``mode`` walks it (None or ``int8``),
        traced once an image length. ``int8`` touches the
        weights alone: the stated layer over weights rounded once a walk, so
        that the check compiles a layer once a length and not twice."""
        import jax

        if mode == "int8":
            stated = self.layer_fn(None)
            return lambda w, x, n: stated(self.int8(w), x, n)
        if mode not in self.jitted:
            self.jitted[mode] = jax.jit(lambda w, x, n: brumby.layer(self.m, w, x, mode), static_argnums=(2,))
        return self.jitted[mode]

    def int8(self, w: dict) -> dict:
        """A layer's leaves with the matrices its products read held in int8 (``brumby.MATRICES``)."""
        import jax

        if "int8" not in self.jitted:
            self.jitted["int8"] = jax.jit(lambda w: {k: brumby._low(v, "int8") if k in brumby.MATRICES else v
                                                     for k, v in w.items()})
        if self.int8_of[0] is not w:
            self.int8_of = (w, self.jitted["int8"](w))
        return self.int8_of[1]


def walk(m: dict, weights: Weights, tokens: list[np.ndarray], ids: list[list[int]], read: list[slice],
         modes: tuple = (None,)) -> dict:
    """For each of ``modes`` (None: the reference itself), per image the
    distributions of its positions ``read``: layer by layer over all the
    images and all the modes, so that a layer's weights are made and sent
    to the device once a walk."""
    import jax

    patch = weights.device("embed/patch")["embed/patch"]
    embedded = [brumby.embed(patch, t, weights.token_rows(i)) for t, i in zip(tokens, ids)]
    xs = {c: list(embedded) for c in modes}
    for l in range(m["num_hidden_layers"]):
        w = jax.device_put(weights.layer(l))
        xs = {c: [weights.layer_fn(c)(w, x, len(t)) for x, t in zip(xs[c], tokens)] for c in modes}
        jax.block_until_ready(list(xs.values()))
        del w
    head = weights.device("final_norm", "head")
    return {c: [np.asarray(brumby.head_probs(m, head, x[r])) for x, r in zip(xs[c], read)] for c in modes}


def greedy(m: dict, weights: Weights, tokens: list[np.ndarray], control: str, steps: int) -> list[list]:
    """What a server computing as ``control`` would answer in its ``steps``
    steps, every image at once."""
    import jax

    patch = weights.device("embed/patch")["embed/patch"]
    head = weights.device("final_norm", "head")
    answers: list[list] = [[] for _ in tokens]

    def rows_after(probs):
        p, c = jax.lax.top_k(probs, m["topk"])
        for a, ps, cs in zip(answers, np.asarray(p), np.asarray(c)):
            a.append([[int(i), float(v)] for i, v in zip(cs, ps)])
        if len(answers[0]) < steps:
            return weights.token_rows([a[-1][0][0] for a in answers])

    brumby.recurrent(m, lambda l: jax.device_put(weights.layer(l)), head,
                     [brumby.embed(patch, t, ()) for t in tokens], steps, rows_after, control)
    return answers


def main() -> int:
    doc = json.load(sys.stdin)
    import jax

    check.compile_cache()
    m, control = doc["model"], doc.get("control")
    if control and control not in brumby.CONTROLS:
        raise ValueError(f"unknown control {control!r}: one of {brumby.CONTROLS}")
    weights = Weights(m, doc["seed"], keep=bool(control))
    steps = m["answer_steps"]
    tokens = [brumby.patches(check.pixels(item), m["patch"]) for item in doc["items"]]
    served = greedy(m, weights, tokens, control, steps) if control else [item["served"] for item in doc["items"]]
    prior = [[int(step[0][0]) for step in a[:steps - 1]] for a in served]
    walked = walk(m, weights, tokens, prior, [slice(len(t) - 1, len(t) - 1 + steps) for t in tokens],
                  (None, "int8"))
    ref, low = (np.concatenate(walked[c]) for c in (None, "int8"))
    pairs = [[(int(c), float(v)) for c, v in step] for a in served for step in a]
    values = check.compare(ref, pairs)
    values["int8_weight_share"] = int8_share(ref, low, pairs)
    print(json.dumps(check.answer(values, doc["limits"], len(tokens), jax.devices()[0].platform)))
    return 0


if __name__ == "__main__":
    sys.exit(main())
