"""What every ``task: "generate"`` family of this zoo keeps to, and the
few blocks they share.

A family is one module of this package under its zoo name
(``longcat_flash``, ``nemotron_h``): functional, no flax, its sizes the
model's JSON (``ModelConfig.decoder``). :func:`family` finds it by that
name; ``adapter.decoder_converted`` asks it for no more than this:

- ``Config`` with ``Config.from_dict(decoder)``, ``token_slots(canvas)``,
  ``patch``, ``answer_steps``, ``max_token_slots``;
- ``param_shapes(c)``, ``leaf_table(c)``, ``init_params(c, seed)``;
- ``answer(c, params, tokens, lengths, topk)`` -> (scores [B, steps, k],
  ids [B, steps, k], counters [len(COUNTERS)]), run inside ``jit_serve``;
- ``COUNTERS``: what ``answer`` counts a call, by name
  (``/stats -> batcher.lifecycle.<name>_total`` sums them over batches).

Shared blocks: the residual stream is float32; what a matrix product reads
(a normed activation, a weight) is the served dtype, and every product
accumulates in float32.
"""

from __future__ import annotations

import dataclasses
import importlib

import jax
import jax.numpy as jnp

from ..ops import experts as experts_op

FFN_CHUNK = 4096   # tokens of one pass of a dense FFN: bounds its hidden activations (100 MB a chunk at width 12,288)


def families() -> list[str]:
    """The zoo's ``task: "generate"`` entries, by name."""
    from . import _ZOO

    return [name for name, spec in _ZOO.items() if spec.task == "generate"]


def family(name: str | None = None, decoder: dict | None = None):
    """The module of the ``task: "generate"`` zoo entry ``name``. Without a
    name: the one family whose ``Config`` states every key of ``decoder``
    (a caller that holds only the sizes)."""
    from . import get

    if name is None:
        keys = set(decoder or ())
        fits = [n for n in families() if keys <= {f.name for f in dataclasses.fields(family(n).Config)}]
        if len(fits) != 1:
            raise ValueError(f"decoder sizes {sorted(keys)} are those of {fits or 'no'} family: name the zoo entry")
        name = fits[0]
    if get(name).task != "generate":
        raise ValueError(f"zoo model '{name}' is no token decoder (task {get(name).task!r})")
    return importlib.import_module(f"{__package__}.{name}")


def config_from(cls, d: dict):
    """``cls`` from the keys of ``d`` that are its fields (a model's JSON may say more than the program reads)."""
    names = {f.name for f in dataclasses.fields(cls)}
    return cls(**{k: v for k, v in d.items() if k in names})


def rmsnorm(x, gain, eps: float, dtype=None):
    """In float32 whatever comes in; out in ``dtype`` (``x``'s unless given)."""
    x32 = x.astype(jnp.float32)
    y = x32 * jax.lax.rsqrt(jnp.mean(x32 * x32, axis=-1, keepdims=True) + eps)
    return (y * gain.astype(jnp.float32)).astype(dtype or x.dtype)


def out(x, w):
    """A block's last product, in float32: it is added to the residual stream, which stays float32."""
    return jnp.dot(x, w, preferred_element_type=jnp.float32)


def mm(x, w):
    return jnp.dot(x, w, preferred_element_type=jnp.float32).astype(x.dtype)


def dense_ffn(x, w_gate, w_up, w_down):
    """A dense SwiGLU on ``x`` of any leading shape, float32 out for the
    residual stream, in passes of ``FFN_CHUNK`` tokens where they divide."""
    one = lambda z: experts_op.swiglu(z, w_gate, w_up, w_down)
    flat = x.reshape(-1, x.shape[-1])
    if flat.shape[0] <= FFN_CHUNK or flat.shape[0] % FFN_CHUNK:
        return one(x)
    return jax.lax.map(one, flat.reshape(-1, FFN_CHUNK, x.shape[-1])).reshape(x.shape)


def select_top(x, k: int):
    """The ``k`` largest of ``x`` along its last axis and their indices,
    equal to ``jax.lax.top_k(x, k)`` bit for bit where ``x`` holds no NaN
    and no -0 (a softmax's probabilities hold neither; ``lax.top_k`` ranks
    +0 above -0, this takes them as one value): of equal values the lower
    index comes first. Nothing is sorted: ``k`` passes over ``x``, each
    the largest value still left, then the lowest index that holds it; what
    is left is what comes after the last pick in that order."""
    pos = jax.lax.broadcasted_iota(jnp.int32, x.shape, x.ndim - 1)
    scores, ids = [], []
    for _ in range(k):
        left = True if not ids else (x < scores[-1]) | ((x == scores[-1]) & (pos > ids[-1]))
        best = jnp.max(jnp.where(left, x, -jnp.inf), axis=-1, keepdims=True)
        scores.append(best)
        ids.append(jnp.min(jnp.where(left & (x == best), pos, x.shape[-1]), axis=-1, keepdims=True))
    return jnp.concatenate(scores, axis=-1), jnp.concatenate(ids, axis=-1)


def top(hidden, final_norm, head, eps: float, topk: int):
    """Final norm, head, softmax over every id held in float32, the
    ``topk`` largest probabilities and their ids by :func:`select_top`:
    exact, with no sort of the vocabulary, the lower id first of equal
    probabilities (as ``jax.lax.top_k``)."""
    with jax.named_scope("head"):
        hn = rmsnorm(hidden, final_norm, eps, head.dtype)
        logits = jnp.dot(hn, head, preferred_element_type=jnp.float32)
        return select_top(jax.nn.softmax(logits, axis=-1), min(topk, logits.shape[-1]))


def layer_params(params: dict, l: int) -> dict:
    """Layer ``l``'s parameters, named without their ``layer<l>/`` prefix."""
    pre = f"layer{l}/"
    return {k[len(pre):]: v for k, v in params.items() if k.startswith(pre)}
