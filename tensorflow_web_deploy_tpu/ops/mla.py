"""Latent attention (MLA) over token sequences: rotary positions, the
causal core in two forms, and the absorbed step against the latent cache.

The core takes queries and keys in two parts, one without position
(``q_n``, ``k_n``: per head) and one rotary (``q_r`` per head, ``k_r`` one
key for all heads), and values narrower than the keys (192 against 128 at
the published sizes). ``lengths`` [B] is how many of a row's ``T`` token
slots hold real tokens; the rest is padding, masked as keys and zero as
queries.

- :func:`blocked_core` walks key blocks with a running maximum and sum
  (online softmax) in ``jax.numpy``: never more than ``[B, H, T, block]``
  at once, on any backend.
- :func:`pallas_core` is the same walk as one Mosaic kernel,
  ``mla_prefill`` (its name in a device trace): grid (row, head, query
  block, key block), running maximum, sum and accumulator in VMEM, blocks
  above the diagonal or past the row's length neither computed nor fetched.

:func:`core` picks by platform: the kernel on a TPU, the blocked walk
elsewhere.

:func:`absorbed_step` is one new token a row against the cache of latents
(``c_kv`` 512 and ``k_r`` 64 values a token): the key up-projection is
folded into the query and the value up-projection applied after the
weighted sum, so no per-head key or value of a cached token is ever formed.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG = -1e30  # a masked score: finite, so a fully masked row yields zeros and no NaN


def rope(x, positions, theta: float):
    """Rotary positions on the last axis of ``x`` [..., T, (heads,) d], the
    pairs being the two halves (i, i + d/2). ``positions`` [..., T] int."""
    d = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    ang = positions.astype(jnp.float32)[..., None] * inv          # [..., T, d/2]
    if x.ndim == ang.ndim + 1:                                      # a heads axis before d
        ang = ang[..., None, :]
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., : d // 2].astype(jnp.float32), x[..., d // 2:].astype(jnp.float32)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1).astype(x.dtype)


def _mask(t_q, t_k, q0, k0, lengths):
    """[B, 1, t_q, t_k]: key at or before the query, and inside the row."""
    rows = q0 + jnp.arange(t_q)[:, None]
    cols = k0 + jnp.arange(t_k)[None, :]
    return (cols <= rows)[None, None] & (cols[None, None] < lengths[:, None, None, None])


def blocked_core(q_n, q_r, k_n, k_r, v, lengths, scale: float, block: int = 512, precision=None):
    """q_n, k_n [B, H, T, dn]; q_r [B, H, T, dr]; k_r [B, T, dr]; v [B, H, T, dv].
    The numbers of a full ``[B, H, T, T]`` softmax, a block of keys at a time."""
    b, h, t, dv = v.shape
    block = min(block, t)
    n_blocks = -(-t // block)
    pad = n_blocks * block - t
    if pad:   # a length that is no multiple of the block: padded keys lie past every row's length
        k_n, v = (jnp.pad(a, ((0, 0), (0, 0), (0, pad), (0, 0))) for a in (k_n, v))
        k_r = jnp.pad(k_r, ((0, 0), (0, pad), (0, 0)))

    def step(carry, j):
        m, l, acc = carry
        k0 = j * block
        kn = jax.lax.dynamic_slice_in_dim(k_n, k0, block, 2)
        kr = jax.lax.dynamic_slice_in_dim(k_r, k0, block, 1)
        vv = jax.lax.dynamic_slice_in_dim(v, k0, block, 2)
        s = (jnp.einsum("bhqd,bhkd->bhqk", q_n, kn, precision=precision, preferred_element_type=jnp.float32)
             + jnp.einsum("bhqd,bkd->bhqk", q_r, kr, precision=precision, preferred_element_type=jnp.float32))
        s = jnp.where(_mask(t, block, 0, k0, lengths), s * scale, NEG)
        m_new = jnp.maximum(m, s.max(-1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m - m_new)
        l = alpha * l + p.sum(-1, keepdims=True)
        acc = alpha * acc + jnp.einsum("bhqk,bhkd->bhqd", p.astype(v.dtype), vv, precision=precision,
                                       preferred_element_type=jnp.float32)
        return (m_new, l, acc), None

    init = (jnp.full((b, h, t, 1), NEG, jnp.float32), jnp.zeros((b, h, t, 1), jnp.float32),
            jnp.zeros((b, h, t, dv), jnp.float32))
    (m, l, acc), _ = jax.lax.scan(step, init, jnp.arange(n_blocks))
    valid = (jnp.arange(t)[None, :] < lengths[:, None])[:, None, :, None]
    return jnp.where(valid, acc / jnp.maximum(l, 1e-30), 0.0).astype(v.dtype)


# ------------------------------------------------------------ the Mosaic kernel

def pick_block(t: int) -> int:
    """The largest block the kernel walks ``t`` token slots in: whole blocks
    only, and big enough that a grid step is matrix work and not overhead."""
    for b in (768, 512, 384, 256, 128):
        if t % b == 0:
            return b
    if t % 8 == 0 and t <= 1024:
        return t
    raise ValueError(f"mla_prefill: {t} token slots are no multiple of 128 (nor one small block)")


def _prefill_kernel(lens_ref, qn_ref, qr_ref, kn_ref, kr_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
                    block: int, scale: float):
    b, qi, kj = pl.program_id(0), pl.program_id(2), pl.program_id(3)
    n = lens_ref[b]

    @pl.when(kj == 0)
    def _():
        m_ref[...] = jnp.full_like(m_ref, NEG)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # keys of this block that some query of this block may see, in a row this long
    @pl.when((kj <= qi) & (kj * block < n) & (qi * block < n))
    def _():
        s = jax.lax.dot_general(qn_ref[0, 0], kn_ref[0, 0], (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        s += jax.lax.dot_general(qr_ref[0, 0], kr_ref[0], (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        rows = qi * block + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
        cols = kj * block + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        s = jnp.where((cols <= rows) & (cols < n), s * scale, NEG)
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
        rows = qi * block + jax.lax.broadcasted_iota(jnp.int32, acc_ref.shape, 0)
        out = acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)
        o_ref[0, 0] = jnp.where(rows < n, out, 0.0).astype(o_ref.dtype)


def pallas_core(q_n, q_r, k_n, k_r, v, lengths, scale: float, interpret: bool = False):
    b, h, t, dn = q_n.shape
    dr, dv = q_r.shape[-1], v.shape[-1]
    block = pick_block(t)
    nb = t // block

    def last_key_block(bi, qi, lens):
        # the last key block that query block ``qi`` of row ``bi`` needs; steps past it name
        # the same block again, so nothing is fetched for them
        return jnp.minimum(qi, jnp.maximum(lens[bi] - 1, 0) // block)

    per_head_q = lambda d: pl.BlockSpec((1, 1, block, d), lambda bi, hi, qi, kj, lens: (bi, hi, qi, 0))
    per_head_k = lambda d: pl.BlockSpec(
        (1, 1, block, d), lambda bi, hi, qi, kj, lens: (bi, hi, jnp.minimum(kj, last_key_block(bi, qi, lens)), 0))
    shared_k = pl.BlockSpec(
        (1, block, dr), lambda bi, hi, qi, kj, lens: (bi, jnp.minimum(kj, last_key_block(bi, qi, lens)), 0))
    return pl.pallas_call(
        functools.partial(_prefill_kernel, block=block, scale=scale),
        name="mla_prefill",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(b, h, nb, nb),
            in_specs=[per_head_q(dn), per_head_q(dr), per_head_k(dn), shared_k, per_head_k(dv)],
            out_specs=per_head_q(dv),
            scratch_shapes=[pltpu.VMEM((block, 1), jnp.float32), pltpu.VMEM((block, 1), jnp.float32),
                            pltpu.VMEM((block, dv), jnp.float32)]),
        out_shape=jax.ShapeDtypeStruct((b, h, t, dv), v.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(lengths.astype(jnp.int32), q_n, q_r, k_n, k_r, v)


def core(q_n, q_r, k_n, k_r, v, lengths, scale: float):
    """The causal core by platform: ``mla_prefill`` on a TPU, the blocked
    walk elsewhere."""
    if jax.default_backend() == "tpu":
        return pallas_core(q_n, q_r, k_n, k_r, v, lengths, scale)
    return blocked_core(q_n, q_r, k_n, k_r, v, lengths, scale)


def absorbed_step(q_n, q_r, w_uk, w_uv, cache_c, cache_r, tail_c, tail_r, lengths, n_tail, scale: float):
    """One new token a row against the latent cache.

    q_n [B, H, dn], q_r [B, H, dr]: the new token's queries; w_uk, w_uv
    [C, H, dn|dv]: the key and value up-projections; cache_c [B, T, C],
    cache_r [B, T, dr]: the image tokens' latents (``lengths`` of them real);
    tail_c [B, S, C], tail_r [B, S, dr]: the answer tokens' latents, of
    which the first ``n_tail`` (this token's own among them) are written.
    Returns [B, H, dv]."""
    f32 = jnp.float32
    q_lat = jnp.einsum("bhd,chd->bhc", q_n, w_uk, preferred_element_type=f32).astype(cache_c.dtype)

    def scores(c, r):
        return (jnp.einsum("bhc,btc->bht", q_lat, c, preferred_element_type=f32)
                + jnp.einsum("bhd,btd->bht", q_r, r, preferred_element_type=f32)) * scale

    t, s = cache_c.shape[1], tail_c.shape[1]
    s_img = jnp.where((jnp.arange(t)[None, :] < lengths[:, None])[:, None, :], scores(cache_c, cache_r), NEG)
    s_tail = jnp.where((jnp.arange(s) < n_tail)[None, None, :], scores(tail_c, tail_r), NEG)
    p = jax.nn.softmax(jnp.concatenate([s_img, s_tail], axis=-1), axis=-1).astype(cache_c.dtype)
    o_lat = (jnp.einsum("bht,btc->bhc", p[..., :t], cache_c, preferred_element_type=f32)
             + jnp.einsum("bht,btc->bhc", p[..., t:], tail_c, preferred_element_type=f32))
    return jnp.einsum("bhc,chd->bhd", o_lat.astype(w_uv.dtype), w_uv, preferred_element_type=f32)
