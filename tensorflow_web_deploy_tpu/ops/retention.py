"""Gated power retention of degree 2 over right-padded rows: the chunked form,
its Mosaic kernel, and one token's step against the carried state (Gelada,
Buckman, Zhang, Bhaskar, "Scaling Context Requires Rethinking Attention",
arXiv:2507.04239: power attention, its gating and its chunked form).

Per key/value head ``j`` and each of the ``R`` query heads ``h`` that read it,
with ``g_t`` the head's gate at token ``t`` (``log g`` given) and ``phi`` the
degree-2 feature map (:func:`features`, ``phi(q) . phi(k) = (q . k)**2``)::

    S_t = g_t S_(t-1) + v_t phi(k_t)'       S [d, D] float32, S_(-1) = 0
    z_t = g_t z_(t-1) + phi(k_t)            z [D] float32
    y_t = S_t phi(q_t) / (z_t . phi(q_t) + eps)

which is the attention form ``y_t = sum_(s<=t) e^(G_t - G_s) (q_t . k_s)**2
v_s / (sum_(s<=t) e^(G_t - G_s) (q_t . k_s)**2 + eps)``, ``G`` the running
sum of ``log g``. Chunks of ``C`` slots; inside a chunk, with ``cs`` the
running sum of ``log g`` from the chunk's start, the chunk's own part is
``(Q K')**2`` under the decay mask (no ``phi``), the carried part
``e^cs (phi(Q) S_prev)``, and the state at the chunk's end
``e^cs_last S_prev + sum_s e^(cs_last - cs_s) v_s phi(k_s)'``.

**The state's layout** is values by features, ``S [d, D]`` and ``z [1, D]``
apart: the features lie along the lanes (``D`` is whole lane blocks) and the
normaliser is no value column (``[D, d + 1]`` would pad to 256 lanes and
double the state).

**The feature map** is the symmetric one over a staircase of sublane tiles:
for the dims ``a`` of each block of 8, the products ``u_a u_b`` with ``b``
from the block's first dim to ``d``, weighted 0 below the diagonal (``b <
a``), 1 on it and ``sqrt 2`` above, so that the dot product of two is
``(q . k)**2`` exactly. ``D`` is 8,704 at ``d`` 128 (the minimal symmetric
map is 8,256: 5.4% more, the cost of building it from whole tiles in the
kernel).

**Padding.** A slot at or past ``lengths[b]`` has ``log g = 0`` and ``k = 0``
(the caller's): it neither decays nor adds to the state, so the state after
the last chunk *is* the state after the row's last real token. Its ``y`` is
finite and nobody's.

- :func:`chunked` is the walk in ``jax.numpy`` (a ``lax.scan`` over chunks).
- :func:`pallas_prefill` is the same as one Mosaic kernel,
  ``retention_prefill``: grid (row, key/value head, chunk), a grid step one
  chunk of a key/value head's ``R`` query heads, its state and normaliser in
  VMEM scratch across the row's chunks, a chunk with no real token neither
  fetched nor computed; the final state is written once.
- :func:`step` is one token a row in ``jax.numpy``; :func:`pallas_step` the
  kernel ``retention_step``: a grid step reads one key/value head's state
  once for its ``R`` query heads and writes it back **in place**
  (``input_output_aliases``), elementwise in float32 on the vector unit.
- :func:`prefill` and :func:`decode` pick by platform.

Products read the inputs' dtype and accumulate in float32; decays, running
sums, the state and the normaliser are float32.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import ssd

TILE = 8          # the sublane tile the feature map is built of
EPS = 1e-6        # the normaliser's floor


def feature_count(d: int) -> int:
    """``D``: the staircase's products for head width ``d``."""
    return sum(TILE * (d - TILE * i) for i in range(d // TILE))


def features(u):
    """``phi(u)`` [..., D] float32 of ``u`` [..., d]: ``phi(q) . phi(k) = (q . k)**2``; the kernel's
    :func:`_features_t`, turned to lie along the last axis (the one definition of the map)."""
    d = u.shape[-1]
    assert d % TILE == 0, f"head width {d} is no multiple of {TILE}"
    u_t = u.reshape(-1, d).astype(jnp.float32).T                           # [d, n]
    return jnp.concatenate(_features_t(u_t), axis=0).T.reshape(*u.shape[:-1], -1)


def _features_t(u_t):
    """``phi(U)'`` [D, n] float32 of ``U'`` [d, n] (dims on sublanes), as parts: for each block of 8 dims ``a``
    their products with every dim ``b`` from the block's first on, as one broadcast, weighted 0 where ``b < a``,
    1 where ``b == a`` and sqrt 2 where ``b > a``; the blocks one after another."""
    d, n = u_t.shape
    parts = []
    for i in range(d // TILE):
        lo = TILE * i
        a = u_t[lo:lo + TILE][:, None, :]
        b = u_t[lo:][None, :, :]
        rows_a = lo + jax.lax.broadcasted_iota(jnp.int32, (TILE, d - lo, n), 0)
        rows_b = lo + jax.lax.broadcasted_iota(jnp.int32, (TILE, d - lo, n), 1)
        w = jnp.where(rows_b > rows_a, np.float32(np.sqrt(2.0)), jnp.where(rows_b == rows_a, 1.0, 0.0))
        parts.append((a * b * w).reshape(TILE * (d - lo), n))
    return parts


# ------------------------------------------------------------------ the walk

def chunked(q, k, v, log_g, *, chunk: int, eps: float = EPS, precision=None):
    """``q`` [B, T, G, R, d], ``k`` and ``v`` [B, T, G, d] (zero ``k`` at
    padding), ``log_g`` [B, T, G] float32 (zero at padding); ``T`` a multiple
    of ``chunk``. Returns (y [B, T, G, R, d] in ``v``'s dtype, S [B, G, d, D]
    and z [B, G, 1, D] float32 after the last slot)."""
    b, t, g, r, d = q.shape
    nc, f32, dt = t // chunk, jnp.float32, v.dtype
    n_feat = feature_count(d)
    _, cs = ssd.decays(log_g, None, chunk)                                 # [B, G, nc, C]
    causal = jnp.tril(jnp.ones((chunk, chunk), bool))
    split = lambda x: jnp.moveaxis(x.reshape(b, nc, chunk, *x.shape[2:]), 1, 0)

    def one(carry, inputs):
        s_prev, z_prev = carry                                             # [B,G,d,D] [B,G,1,D]
        q_c, k_c, v_c, cs_c = inputs                                       # [B,C,G,R,d] [B,C,G,d] [B,C,G,d] [B,G,C]
        scores = jnp.einsum("bigrd,bjgd->bgrij", q_c, k_c, precision=precision, preferred_element_type=f32)
        decay = jnp.exp(jnp.where(causal, cs_c[..., :, None] - cs_c[..., None, :], -jnp.inf))      # [B,G,C,C]
        p = scores * scores * decay[:, :, None]
        num = jnp.einsum("bgrij,bjgd->bigrd", p.astype(dt), v_c, precision=precision, preferred_element_type=f32)
        den = p.sum(-1).transpose(0, 3, 1, 2)                              # [B,C,G,R]
        phi_q = features(q_c).astype(dt)                                   # [B,C,G,R,D]
        e = jnp.exp(cs_c).transpose(0, 2, 1)[..., None]                    # [B,C,G,1]
        num += e[..., None] * jnp.einsum("bigrf,bgvf->bigrv", phi_q, s_prev.astype(dt), precision=precision,
                                         preferred_element_type=f32)
        den += e * jnp.einsum("bigrf,bgf->bigr", phi_q, z_prev[:, :, 0].astype(dt), precision=precision,
                              preferred_element_type=f32)
        y = num / (den[..., None] + eps)
        w = jnp.exp(cs_c[..., -1:] - cs_c).transpose(0, 2, 1)              # [B,C,G]
        phi_k = features(k_c).astype(dt)                                   # [B,C,G,D]
        keep = jnp.exp(cs_c[..., -1])[..., None, None]
        s_new = keep * s_prev + jnp.einsum("bjgv,bjgf->bgvf", (v_c.astype(f32) * w[..., None]).astype(dt), phi_k,
                                           precision=precision, preferred_element_type=f32)
        z_new = keep * z_prev + jnp.einsum("bjg,bjgf->bgf", w.astype(dt), phi_k, precision=precision,
                                           preferred_element_type=f32)[:, :, None]
        return (s_new, z_new), y.astype(dt)

    carry0 = (jnp.zeros((b, g, d, n_feat), f32), jnp.zeros((b, g, 1, n_feat), f32))
    (s, z), y = jax.lax.scan(one, carry0, (split(q), split(k), split(v), jnp.moveaxis(cs, 2, 0)))
    return jnp.moveaxis(y, 0, 1).reshape(b, t, g, r, d), s, z


def step(q, k, v, log_g, s, z, *, eps: float = EPS):
    """One token a row: ``q`` [B, G, R, d], ``k`` and ``v`` [B, G, d],
    ``log_g`` [B, G], ``s`` [B, G, d, D], ``z`` [B, G, 1, D]. Returns (y [B,
    G, R, d] float32, s, z): the token goes in first, then is read out."""
    f32 = jnp.float32
    g = jnp.exp(log_g.astype(f32))[..., None, None]
    phi_k, phi_q = features(k)[:, :, None], features(q)                   # [B,G,1,D] [B,G,R,D]
    s = g * s + v.astype(f32)[..., None] * phi_k
    z = g * z + phi_k
    num = jnp.einsum("bgrf,bgvf->bgrv", phi_q, s, precision=jax.lax.Precision.HIGHEST)
    den = jnp.sum(phi_q * z, axis=-1)
    return num / (den[..., None] + eps), s, z


# ------------------------------------------------------------ the Mosaic kernels

def _prefill_kernel(lens_ref, qt_ref, kt_ref, k_ref, vt_ref, cs_ref, yt_ref, s_hbm, z_ref,
                    sz_ref, szb_ref, phik_ref, phiq_ref, sem, *, chunk: int, per: int, d: int, eps: float):
    """One chunk of one key/value head's ``per`` query heads, everything
    transposed (tokens along the lanes): scores ``K Q'``, values ``V' P'``,
    the carried part ``[S; z] phi(Q)'``, the update ``[V' w; w] phi(K)``."""
    bi, gi, ci = pl.program_id(0), pl.program_id(1), pl.program_id(2)
    n_live = (lens_ref[bi] + chunk - 1) // chunk
    f32, dt = jnp.float32, vt_ref.dtype
    rows = sz_ref.shape[0]                                                 # d values, the normaliser, zeros to 8

    @pl.when(ci == 0)
    def _():
        sz_ref[...] = jnp.zeros_like(sz_ref)

    @pl.when(ci < n_live)
    def _():
        cs_row = cs_ref[0, 0, pl.ds(ci, 1), :]                             # [1, C]: a query's (lanes)
        cs_col = ssd._column(cs_row, chunk)                                # [C, 1]: a key's (sublanes)
        earlier = (jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
                   <= jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1))
        decay_t = jnp.exp(jnp.where(earlier, cs_row - cs_col, -jnp.inf))   # [key, query]
        szb_ref[...] = sz_ref[...].astype(dt)                              # the carried state, read once a chunk
        off = 0
        for part in _features_t(kt_ref[0, 0].astype(f32)):
            phik_ref[pl.ds(off, part.shape[0]), :] = part.astype(dt)
            off += part.shape[0]
        k_rows, v_t = k_ref[0, 0], vt_ref[0, 0]                            # [C, d] [d, C]
        e = jnp.exp(cs_row)
        for r in range(per):
            q_t = qt_ref[0, 0, r]                                          # [d, C]
            s_t = jnp.dot(k_rows, q_t, preferred_element_type=f32)         # [key, query]
            p_t = s_t * s_t * decay_t
            num = jnp.dot(v_t, p_t.astype(dt), preferred_element_type=f32)                      # [d, C]
            den = jnp.sum(p_t, axis=0, keepdims=True)                                          # [1, C]
            off = 0
            for part in _features_t(q_t.astype(f32)):
                phiq_ref[pl.ds(off, part.shape[0]), :] = part.astype(dt)
                off += part.shape[0]
            carried = jnp.dot(szb_ref[...], phiq_ref[...], preferred_element_type=f32)         # [rows, C]
            num = num + e * carried[:d]
            den = den + e * carried[d:d + 1]
            yt_ref[0, 0, r] = (num / (den + eps)).astype(yt_ref.dtype)
        cs_last = cs_row[:, chunk - 1:chunk]
        w = jnp.exp(cs_last - cs_row)                                      # [1, C]
        fill = jnp.where(jax.lax.broadcasted_iota(jnp.int32, (rows - d, chunk), 0) == 0, w, 0.0)
        vw = jnp.concatenate([v_t.astype(f32) * w, fill], axis=0).astype(dt)                   # [rows, C]
        keep = jnp.exp(jnp.broadcast_to(cs_last, (1, sz_ref.shape[1])))   # along the lanes first: [1,1] -> [1, D]
        sz_ref[...] = keep * sz_ref[...] + jax.lax.dot_general(
            vw, phik_ref[...], (((1,), (1,)), ((), ())), preferred_element_type=f32)

    @pl.when(ci >= n_live)
    def _():
        yt_ref[...] = jnp.zeros_like(yt_ref)

    @pl.when(ci == pl.num_programs(2) - 1)
    def _():
        to_s = pltpu.make_async_copy(sz_ref.at[pl.ds(0, d)], s_hbm.at[bi, gi], sem.at[0])
        to_s.start()
        z_ref[0, 0] = sz_ref[pl.ds(d, 1), :]
        to_s.wait()


def pallas_prefill(q, k, v, log_g, lengths, *, chunk: int, eps: float = EPS, interpret: bool = False):
    """:func:`chunked` as the kernel ``retention_prefill``; ``lengths`` [B]
    says which chunks of a row are live."""
    b, t, g, r, d = q.shape
    nc, n_feat, rows = t // chunk, feature_count(d), d + TILE
    _, cs = ssd.decays(log_g, None, chunk)                                 # [B, G, nc, C]
    q_t = q.transpose(0, 2, 3, 4, 1)                                       # [B, G, R, d, T]
    k_t, v_t = k.transpose(0, 2, 3, 1), v.transpose(0, 2, 3, 1)            # [B, G, d, T]
    k_rows = k.transpose(0, 2, 1, 3)                                       # [B, G, T, d]

    def live(bi, ci, lens):   # a chunk past the row's length names the last live one: nothing is fetched for it
        return jnp.minimum(ci, jnp.maximum((lens[bi] + chunk - 1) // chunk - 1, 0))

    q_spec = pl.BlockSpec((1, 1, r, d, chunk), lambda bi, gi, ci, lens: (bi, gi, 0, 0, live(bi, ci, lens)))
    col_spec = pl.BlockSpec((1, 1, d, chunk), lambda bi, gi, ci, lens: (bi, gi, 0, live(bi, ci, lens)))
    row_spec = pl.BlockSpec((1, 1, chunk, d), lambda bi, gi, ci, lens: (bi, gi, live(bi, ci, lens), 0))
    cs_spec = pl.BlockSpec((1, 1, nc, chunk), lambda bi, gi, ci, lens: (bi, gi, 0, 0))
    y_spec = pl.BlockSpec((1, 1, r, d, chunk), lambda bi, gi, ci, lens: (bi, gi, 0, 0, ci))
    z_spec = pl.BlockSpec((1, 1, 1, n_feat), lambda bi, gi, ci, lens: (bi, gi, 0, 0))
    y_t, s, z = pl.pallas_call(
        functools.partial(_prefill_kernel, chunk=chunk, per=r, d=d, eps=eps),
        name="retention_prefill",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(b, g, nc),
            in_specs=[q_spec, col_spec, row_spec, col_spec, cs_spec],
            out_specs=[y_spec, pl.BlockSpec(memory_space=pl.ANY), z_spec],
            scratch_shapes=[pltpu.VMEM((rows, n_feat), jnp.float32), pltpu.VMEM((rows, n_feat), v.dtype),
                            pltpu.VMEM((n_feat, chunk), v.dtype), pltpu.VMEM((n_feat, chunk), v.dtype),
                            pltpu.SemaphoreType.DMA((1,))]),
        out_shape=[jax.ShapeDtypeStruct(q_t.shape, v.dtype), jax.ShapeDtypeStruct((b, g, d, n_feat), jnp.float32),
                   jax.ShapeDtypeStruct((b, g, 1, n_feat), jnp.float32)],
        compiler_params=pltpu.CompilerParams(dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(lengths.astype(jnp.int32), q_t, k_t, k_rows, v_t, cs)
    return y_t.transpose(0, 4, 1, 2, 3), s, z


def _step_kernel(g_ref, phiq_ref, phik_ref, v_ref, s_ref, z_ref, y_ref, s_out, z_out, *, per: int, eps: float):
    """One row's token through one key/value head's state: the state read
    once, updated, written back to where it came from; ``per`` read-outs
    from it, elementwise and summed along the lanes, in float32."""
    bi, gi = pl.program_id(0), pl.program_id(1)
    gate = g_ref[bi, gi]
    phi_k = phik_ref[0, 0]                                                 # [1, D]
    s_out[0, 0] = gate * s_ref[0, 0] + v_ref[0, 0] * phi_k                 # [d, 1] x [1, D]
    z_out[0, 0] = gate * z_ref[0, 0] + phi_k
    s_new, z_new = s_out[0, 0], z_out[0, 0]
    lanes = y_ref.shape[-1]
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, lanes), 1)
    num = jnp.zeros((s_new.shape[0], lanes), jnp.float32)
    den = jnp.zeros((1, lanes), jnp.float32)
    for r in range(per):
        phi_q = phiq_ref[0, 0, pl.ds(r, 1), :]                             # [1, D]
        num = jnp.where(lane == r, jnp.sum(s_new * phi_q, axis=1, keepdims=True), num)
        den = jnp.where(lane == r, jnp.sum(z_new * phi_q, axis=1, keepdims=True), den)
    y_ref[0, 0] = num / (den + eps)


def pallas_step(q, k, v, log_g, s, z, *, eps: float = EPS, interpret: bool = False):
    """:func:`step` as the kernel ``retention_step``; ``s`` and ``z`` are
    updated in place."""
    b, g, r, d = q.shape
    n_feat, f32 = s.shape[-1], jnp.float32
    phi_q = features(q)                                                    # [B, G, R, D]
    phi_k = features(k)[:, :, None]                                        # [B, G, 1, D]
    gate = jnp.exp(log_g.astype(f32))
    block = lambda rows, cols: pl.BlockSpec((1, 1, rows, cols), lambda bi, gi: (bi, gi, 0, 0))
    y, s, z = pl.pallas_call(
        functools.partial(_step_kernel, per=r, eps=eps),
        name="retention_step",
        grid=(b, g),
        in_specs=[pl.BlockSpec(memory_space=pltpu.SMEM), block(r, n_feat), block(1, n_feat), block(d, 1),
                  block(d, n_feat), block(1, n_feat)],
        out_specs=[block(d, r), block(d, n_feat), block(1, n_feat)],
        out_shape=[jax.ShapeDtypeStruct((b, g, d, r), f32), jax.ShapeDtypeStruct(s.shape, f32),
                   jax.ShapeDtypeStruct(z.shape, f32)],
        input_output_aliases={4: 1, 5: 2},
        # the state in and out, each double-buffered: 4 x 4.46 MB at d 128
        compiler_params=pltpu.CompilerParams(dimension_semantics=("parallel", "parallel"),
                                             vmem_limit_bytes=48 << 20),
        interpret=interpret,
    )(gate, phi_q, phi_k, v.astype(f32)[..., None], s, z)
    return y.transpose(0, 1, 3, 2), s, z


# ------------------------------------------------------------------ by platform

def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def prefill(q, k, v, log_g, lengths, *, chunk: int):
    """The chunked form by platform: ``retention_prefill`` on a TPU, the walk elsewhere."""
    if _on_tpu():
        return pallas_prefill(q, k, v, log_g, lengths, chunk=chunk)
    return chunked(q, k, v, log_g, chunk=chunk)


def decode(q, k, v, log_g, s, z):
    """One token by platform: ``retention_step`` on a TPU, :func:`step` elsewhere."""
    if _on_tpu():
        return pallas_step(q, k, v, log_g, s, z)
    return step(q, k, v, log_g, s, z)
