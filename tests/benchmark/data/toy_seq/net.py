"""A toy sequence model with nothing convolutional in it, for the tests: an
image's 8 x 8 patches as tokens, one causal attention layer, a router over
four experts of which two answer a token, a head over 48 ids. An answer is
``answer_steps`` distributions: one after the image, then one after each
token that was put first (greedy), appended."""

import jax
import jax.numpy as jnp
import ml_dtypes
import numpy as np

PATCH, DIM, HEADS, EXPERTS, PER_TOKEN, HIDDEN, VOCAB = 8, 32, 2, 4, 2, 64, 48
HI = jax.lax.Precision.HIGHEST
DTYPES = {"bfloat16": ml_dtypes.bfloat16, "float32": np.float32}   # what an export may hold


def shapes(model: dict) -> dict[str, tuple[int, int]]:
    out = {"embed/patch": (PATCH * PATCH * 3, DIM), "embed/token": (VOCAB, DIM), "router": (DIM, EXPERTS),
           "head": (DIM, VOCAB), **{f"attn/{w}": (DIM, DIM) for w in "qkvo"}}
    for e in range(EXPERTS):
        out |= {f"expert/{e}/up": (DIM, HIDDEN), f"expert/{e}/down": (HIDDEN, DIM)}
    return out


def std(name: str, shape) -> float:
    return (3.0 if name == "head" else 1.0) / np.sqrt(shape[0])   # logits a few units wide, as weights.py's


def patches(pixels: np.ndarray) -> np.ndarray:
    """[h, w, 3] uint8 -> [tokens, 192] in [-1, 1]: every whole patch, row by row."""
    h, w = pixels.shape[0] // PATCH, pixels.shape[1] // PATCH
    x = pixels[:h * PATCH, :w * PATCH].reshape(h, PATCH, w, PATCH, 3).transpose(0, 2, 1, 3, 4)
    return x.reshape(h * w, -1).astype(np.float32) / 127.5 - 1.0


def _norm(x):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + 1e-5)


def probs(p: dict, tokens: np.ndarray, ids: list[int]) -> np.ndarray:
    """The distribution after the image's ``tokens`` and the ``ids`` appended."""
    mm = lambda a, b: jnp.matmul(a, b, precision=HI)
    x = mm(tokens, p["embed/patch"])
    if ids:
        x = jnp.concatenate([x, p["embed/token"][np.asarray(ids)]])
    h = _norm(x)
    q, k, v = (mm(h, p[f"attn/{w}"]).reshape(len(x), HEADS, -1).swapaxes(0, 1) for w in "qkv")
    scores = mm(q, k.swapaxes(1, 2)) / np.sqrt(DIM // HEADS)
    scores = jnp.where(jnp.tril(jnp.ones((len(x), len(x)), bool)), scores, -jnp.inf)
    x = x + mm(mm(jax.nn.softmax(scores), v).swapaxes(0, 1).reshape(len(x), DIM), p["attn/o"])
    h = _norm(x)
    gate = jax.nn.sigmoid(mm(h, p["router"]))
    # two experts a token, weighted by their lead over the third, so that an answer is continuous where
    # two gates tie: a plain top-2 flips an expert on a rounding, and the limits would have to cover that
    gate = jnp.maximum(gate - jnp.sort(gate, -1)[:, -PER_TOKEN - 1, None], 0.0)
    gate = gate / gate.sum(-1, keepdims=True)
    x = x + sum(gate[:, e, None] * mm(jax.nn.silu(mm(h, p[f"expert/{e}/up"])), p[f"expert/{e}/down"])
                for e in range(EXPERTS))
    return np.asarray(jax.nn.softmax(mm(_norm(x[-1]), p["head"])))


def answer(p: dict, tokens: np.ndarray, steps: int, topk: int) -> list[list]:
    """What a server of ``p`` answers for one image: ``steps`` top-k lists of [id, score], greedy."""
    out = []
    for _ in range(steps):
        dist = probs(p, tokens, [step[0][0] for step in out])
        out.append([[int(c), float(dist[c])] for c in np.argsort(-dist)[:topk]])
    return out


def stored(w: np.ndarray, tier: str) -> np.ndarray:
    """A leaf as a serving tier holds it, back in float32: bfloat16 rounds
    each value; int8 keeps 255 levels a column."""
    if tier == "int8":
        scale = np.abs(w).max(0) / 127.0
        return (np.rint(w / scale) * scale).astype(np.float32)
    return w.astype(DTYPES[tier]).astype(np.float32)
