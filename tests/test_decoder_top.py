"""The decoders' answer head (models/decoder.py::top): its top-k selection
equals ``jax.lax.top_k`` bit for bit, sorts nothing, and leaves every
family's answers as they were."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tensorflow_web_deploy_tpu.models import brumby, decoder as shared, longcat_flash, nemotron_h
from tensorflow_web_deploy_tpu.ops.image import patch_tokens
from tests import test_brumby, test_longcat_flash, test_nemotron_h

WIDTHS = (1000, 16384, 65536, 151936)   # the classifiers' classes, then the three decoders' vocabularies


def logits(kind: str, rows: int, width: int, seed: int = 0) -> np.ndarray:
    rs = np.random.default_rng(seed)
    if kind == "random":
        return 3.0 * rs.standard_normal((rows, width)).astype(np.float32)
    if kind == "duplicated":        # eight values: the largest is held by about an eighth of the ids
        return rs.integers(0, 8, (rows, width)).astype(np.float32)
    if kind == "all_equal":
        return np.full((rows, width), 0.25, np.float32)
    # "underflow": three ids a row hold the mass, the rest of the row's probabilities are exactly zero
    x = np.full((rows, width), -1e4, np.float32)
    for r in range(rows):
        x[r, rs.choice(width, 3, replace=False)] = (0.0, 0.0, 1.5)
    return x


@functools.partial(jax.jit, static_argnums=1)
def ours(x, k):
    return shared.select_top(jax.nn.softmax(x, axis=-1), k)


@functools.partial(jax.jit, static_argnums=1)
def theirs(x, k):
    return jax.lax.top_k(jax.nn.softmax(x, axis=-1), k)


@pytest.mark.parametrize("kind", ["random", "duplicated", "all_equal", "underflow"])
@pytest.mark.parametrize("width,k", [(w, k) for w in WIDTHS for k in (1, 5)] + [(7, 7)])
@pytest.mark.parametrize("rows", [1, 4, 16])
def test_the_selection_equals_lax_top_k_on_softmax_outputs_bit_for_bit(kind, width, k, rows):
    x = jnp.asarray(logits(kind, rows, width))
    if kind == "underflow":
        assert int((jax.nn.softmax(x, axis=-1) == 0).sum(-1).min()) == width - 3
    (s, i), (s_ref, i_ref) = ours(x, k), theirs(x, k)
    assert s.dtype == s_ref.dtype == jnp.float32 and i.dtype == i_ref.dtype == jnp.int32
    assert s.shape == i.shape == (rows, k)
    np.testing.assert_array_equal(np.asarray(s).view(np.uint32), np.asarray(s_ref).view(np.uint32))
    np.testing.assert_array_equal(np.asarray(i), np.asarray(i_ref))


def _primitives(jaxpr) -> set[str]:
    out = set()
    for eqn in jaxpr.eqns:
        out.add(eqn.primitive.name)
        for sub in jax.core.jaxprs_in_params(eqn.params):
            out |= _primitives(sub)
    return out


def test_the_head_at_brumbys_widths_holds_no_sort_and_no_top_k(monkeypatch):
    """At 16 rows of hidden 5,120 through a [5,120, 151,936] bfloat16 head,
    abstract shapes only: on the CPU ``lax.top_k`` lowers to a custom call,
    not a sort, so the jaxpr is where the absence is pinned."""
    args = (jax.ShapeDtypeStruct((16, 5120), jnp.float32), jax.ShapeDtypeStruct((5120,), jnp.bfloat16),
            jax.ShapeDtypeStruct((5120, 151936), jnp.bfloat16))
    head = lambda: functools.partial(shared.top, eps=1e-6, topk=5)     # a new function a trace: make_jaxpr caches by it
    closed = jax.make_jaxpr(head())(*args)
    assert not {"sort", "top_k"} & _primitives(closed.jaxpr)
    assert [v.aval.shape for v in closed.jaxpr.outvars] == [(16, 5), (16, 5)]
    # the check sees the selection it replaced
    monkeypatch.setattr(shared, "select_top", jax.lax.top_k)
    assert "top_k" in _primitives(jax.make_jaxpr(head())(*args).jaxpr)


@pytest.mark.parametrize("family,tests", [(longcat_flash, test_longcat_flash), (nemotron_h, test_nemotron_h),
                                          (brumby, test_brumby)], ids=lambda v: getattr(v, "__name__", "").rsplit(".")[-1])
def test_every_familys_answers_are_those_of_lax_top_k_at_every_step(family, tests, monkeypatch):
    cfg = tests.CFG
    params = {k: jnp.asarray(v) for k, v in family.init_params(cfg, seed=2**31 + 40).items()}
    _, canvases, hws = tests.images(64, sizes=((64, 48), (40, 56), (64, 64), (8, 8)))
    tokens, lengths = patch_tokens(canvases, hws, cfg.patch)

    def served():
        with jax.default_matmul_precision("highest"):
            return jax.jit(lambda p, t, l: family.answer(cfg, p, t, l, 5))(params, tokens, lengths)

    scores, ids, counters = served()
    monkeypatch.setattr(shared, "select_top", jax.lax.top_k)
    scores_ref, ids_ref, counters_ref = served()
    assert scores.shape == ids.shape == (4, cfg.answer_steps, 5)
    np.testing.assert_array_equal(np.asarray(scores).view(np.uint32), np.asarray(scores_ref).view(np.uint32))
    np.testing.assert_array_equal(np.asarray(ids), np.asarray(ids_ref))
    np.testing.assert_array_equal(np.asarray(counters), np.asarray(counters_ref))
