"""Grouped-query attention over token sequences: the causal core in two
forms, and one token's step against the key/value cache.

``q`` [B, G, R, T, d]: ``G`` key/value heads, each read by ``R`` query heads
(query head ``i`` reads key/value head ``i // R``); ``k``, ``v`` [B, G, T, d].
Positions are the caller's: what carries none (Nemotron-H's attention)
hands its projections over as they are. ``lengths`` [B] of a row's ``T``
slots hold real tokens; the rest is padding, masked as keys and zero as
queries (``ops/mla.py``'s mask, and its discipline: no ``T x T`` array is
ever formed, blocks above the diagonal are skipped, a row stops at its
length; ``mla_prefill`` takes latent-attention operands, two-part queries
and one shared rotary key, so this is its sibling and not its body widened).

- :func:`blocked_core` walks key blocks with a running maximum and sum in
  ``jax.numpy``, on any backend.
- :func:`pallas_core` is the same walk as one Mosaic kernel,
  ``gqa_prefill`` (its name in a device trace): grid (row, key/value head,
  query block, key block), a group's ``R`` query heads as the rows of one
  block (one fetch of a key block serves them all), blocks above the
  diagonal or past the row's length neither computed nor fetched.
- :func:`core` picks by platform.

:func:`decode_step` is one new token a row against the cache: the image's
keys and values (``lengths`` of them real) and the answer's tail so far.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .mla import NEG, _mask


def blocked_core(q, k, v, lengths, scale: float, block: int = 512, precision=None):
    """The numbers of a full ``[B, G, R, T, T]`` softmax, a block of keys at a time."""
    b, g, r, t, d = q.shape
    block = min(block, t)
    n_blocks = -(-t // block)
    pad = n_blocks * block - t
    if pad:   # padded keys lie past every row's length
        k, v = (jnp.pad(a, ((0, 0), (0, 0), (0, pad), (0, 0))) for a in (k, v))

    def step(carry, j):
        m, l, acc = carry
        k0 = j * block
        kk = jax.lax.dynamic_slice_in_dim(k, k0, block, 2)
        vv = jax.lax.dynamic_slice_in_dim(v, k0, block, 2)
        s = jnp.einsum("bgrqd,bgkd->bgrqk", q, kk, precision=precision, preferred_element_type=jnp.float32)
        s = jnp.where(_mask(t, block, 0, k0, lengths)[:, :, None], s * scale, NEG)
        m_new = jnp.maximum(m, s.max(-1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m - m_new)
        l = alpha * l + p.sum(-1, keepdims=True)
        acc = alpha * acc + jnp.einsum("bgrqk,bgkd->bgrqd", p.astype(v.dtype), vv, precision=precision,
                                       preferred_element_type=jnp.float32)
        return (m_new, l, acc), None

    init = (jnp.full((b, g, r, t, 1), NEG, jnp.float32), jnp.zeros((b, g, r, t, 1), jnp.float32),
            jnp.zeros((b, g, r, t, d), jnp.float32))
    (m, l, acc), _ = jax.lax.scan(step, init, jnp.arange(n_blocks))
    valid = (jnp.arange(t)[None, :] < lengths[:, None])[:, None, None, :, None]
    return jnp.where(valid, acc / jnp.maximum(l, 1e-30), 0.0).astype(v.dtype)


# ------------------------------------------------------------ the Mosaic kernel

ROWS = 4096   # the most rows of scores (query heads x slots) one grid step holds: 4 MB in float32 at 256 keys


def pick_block(t: int, per: int) -> int:
    """The block the kernel walks ``t`` token slots in: whole blocks only,
    and with a group's ``per`` query heads as rows no more than ``ROWS``."""
    for b in (512, 256, 128):
        if t % b == 0 and (per * b <= ROWS or b == 128):
            return b
    if t % 8 == 0 and t <= 1024:
        return t
    raise ValueError(f"gqa_prefill: {t} token slots are no multiple of 128 (nor one small block)")


def _prefill_kernel(lens_ref, q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *, block: int, per: int, scale: float):
    b, qi, kj = pl.program_id(0), pl.program_id(2), pl.program_id(3)
    n = lens_ref[b]
    rows_all = per * block

    @pl.when(kj == 0)
    def _():
        m_ref[...] = jnp.full_like(m_ref, NEG)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    def position(shape):   # a row of the block is (query head, slot): its slot
        return qi * block + jax.lax.broadcasted_iota(jnp.int32, (per, block, shape), 1).reshape(rows_all, shape)

    @pl.when((kj <= qi) & (kj * block < n) & (qi * block < n))
    def _():
        q = q_ref[0, 0].reshape(rows_all, q_ref.shape[-1])
        s = jax.lax.dot_general(q, k_ref[0, 0], (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32)
        cols = kj * block + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        s = jnp.where((cols <= position(block)) & (cols < n), s * scale, NEG)
        m_old = m_ref[...]
        m_new = jnp.maximum(m_old, s.max(-1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_old - m_new)
        l_ref[...] = alpha * l_ref[...] + p.sum(-1, keepdims=True)
        acc_ref[...] = alpha * acc_ref[...] + jnp.dot(p.astype(v_ref.dtype), v_ref[0, 0],
                                                      preferred_element_type=jnp.float32)
        m_ref[...] = m_new

    @pl.when(kj == pl.num_programs(3) - 1)
    def _():
        out = acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)
        out = jnp.where(position(acc_ref.shape[-1]) < n, out, 0.0)
        o_ref[0, 0] = out.reshape(per, block, acc_ref.shape[-1]).astype(o_ref.dtype)


def pallas_core(q, k, v, lengths, scale: float, interpret: bool = False):
    b, g, r, t, d = q.shape
    block = pick_block(t, r)
    nb = t // block

    def last_key_block(bi, qi, lens):
        # the last key block that query block ``qi`` of row ``bi`` needs; steps past it name
        # the same block again, so nothing is fetched for them
        return jnp.minimum(qi, jnp.maximum(lens[bi] - 1, 0) // block)

    q_spec = pl.BlockSpec((1, 1, r, block, d), lambda bi, gi, qi, kj, lens: (bi, gi, 0, qi, 0))
    kv_spec = pl.BlockSpec(
        (1, 1, block, d), lambda bi, gi, qi, kj, lens: (bi, gi, jnp.minimum(kj, last_key_block(bi, qi, lens)), 0))
    return pl.pallas_call(
        functools.partial(_prefill_kernel, block=block, per=r, scale=scale),
        name="gqa_prefill",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(b, g, nb, nb),
            in_specs=[q_spec, kv_spec, kv_spec], out_specs=q_spec,
            scratch_shapes=[pltpu.VMEM((r * block, 1), jnp.float32), pltpu.VMEM((r * block, 1), jnp.float32),
                            pltpu.VMEM((r * block, d), jnp.float32)]),
        out_shape=jax.ShapeDtypeStruct(q.shape, v.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel", "arbitrary"), vmem_limit_bytes=48 << 20),
        interpret=interpret,
    )(lengths.astype(jnp.int32), q, k, v)


def core(q, k, v, lengths, scale: float):
    """The causal core by platform: ``gqa_prefill`` on a TPU, the blocked
    walk elsewhere."""
    if jax.default_backend() == "tpu":
        return pallas_core(q, k, v, lengths, scale)
    return blocked_core(q, k, v, lengths, scale)


def decode_step(q, cache_k, cache_v, tail_k, tail_v, lengths, n_tail, scale: float):
    """One new token a row: ``q`` [B, G, R, d] against ``cache_k``/``cache_v``
    [B, G, T, d] (the image's, ``lengths`` of them real) and ``tail_k``/
    ``tail_v`` [B, G, S, d] (the answer's, the first ``n_tail`` written, this
    token's own among them). Returns [B, G, R, d] float32."""
    f32 = jnp.float32
    t, s = cache_k.shape[2], tail_k.shape[2]
    scores = lambda kk: jnp.einsum("bgrd,bgkd->bgrk", q, kk, preferred_element_type=f32) * scale
    s_img = jnp.where((jnp.arange(t)[None, :] < lengths[:, None])[:, None, None, :], scores(cache_k), NEG)
    s_tail = jnp.where((jnp.arange(s) < n_tail)[None, None, None, :], scores(tail_k), NEG)
    p = jax.nn.softmax(jnp.concatenate([s_img, s_tail], axis=-1), axis=-1).astype(cache_v.dtype)
    return (jnp.einsum("bgrk,bgkd->bgrd", p[..., :t], cache_v, preferred_element_type=f32)
            + jnp.einsum("bgrk,bgkd->bgrd", p[..., t:], tail_v, preferred_element_type=f32))
