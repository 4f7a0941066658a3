#!/usr/bin/env python3
"""The toy's check child: ``benchmark/check.py``'s document in, its answer
out. Leaf by leaf from the seed in float32, each image's reference
distribution at every step, after the tokens that the *served* answer put
first; ``logit_rms`` and ``logit_max`` over every (image, step) as
``check.compare`` defines them. The toy is served in float32, so the
control is the step below: ``control: "bfloat16_weights"`` puts the
reference with every leaf stored as bfloat16 in the served answers' place."""

import json
import sys
from pathlib import Path

import numpy as np

sys.path[:0] = [str(Path(__file__).resolve().parents[4]), str(Path(__file__).resolve().parent)]

from benchmark import check  # noqa: E402
from benchmark.reference import leaves  # noqa: E402


def main() -> int:
    doc = json.load(sys.stdin)
    import jax
    import net

    check.compile_cache()
    m, steps = doc["model"], doc["model"]["answer_steps"]
    params = {name: leaves.normal(doc["seed"], name, shape, net.std(name, shape))
              for name, shape in net.shapes(m).items()}
    tokens = [net.patches(check.pixels(item)) for item in doc["items"]]
    served = [item["served"] for item in doc["items"]]
    if doc.get("control") == "bfloat16_weights":
        low = {name: net.stored(v, "bfloat16") for name, v in params.items()}
        served = [net.answer(low, t, steps, m["topk"]) for t in tokens]
    ref, pairs = [], []
    for t, steps_served in zip(tokens, served):
        for s, step in enumerate(steps_served):
            ref.append(net.probs(params, t, [prior[0][0] for prior in steps_served[:s]]))
            pairs.append([(int(c), float(v)) for c, v in step])
    print(json.dumps(check.answer(check.compare(np.stack(ref), pairs), doc["limits"], len(tokens),
                                  jax.devices()[0].platform)))
    return 0


if __name__ == "__main__":
    sys.exit(main())
