"""The Mamba-2 state-space scan over right-padded rows, in its chunked
(state-space dual) form, and one token's step against the carried state.

Per head, with ``a_t = delta_t * A`` (``A`` negative)::

    S_t = exp(a_t) S_(t-1) + delta_t x_t (x) B_t        S [P, N] float32, S_(-1) = 0
    y_t = S_t C_t

(``D * x`` and the gate are the model's). Chunks of ``Q`` slots; inside a
chunk, with ``cs_i`` the running sum of ``a`` from the chunk's start::

    y_i = sum_(j<=i) (C_i . B_j) exp(cs_i - cs_j) delta_j x_j  +  exp(cs_i) (S_prev C_i)
    S_next = exp(cs_last) S_prev + sum_j exp(cs_last - cs_j) delta_j x_j (x) B_j

**Padding.** ``lengths`` [B] of a row's ``T`` slots hold real tokens. A slot
at or past ``lengths[b]`` gets ``delta = 0``: decay 1, input 0, so the state
after the last chunk *is* the state after the row's last real token, which
is what the answer steps go on from. What ``y`` holds at such a slot is
finite and nobody's: nothing causal reads it.

- :func:`chunked_scan` is the walk in ``jax.numpy`` (a ``lax.scan`` over
  chunks), on any backend.
- :func:`pallas_scan` is the same as one Mosaic kernel, ``ssd_prefill`` (its
  name in a device trace): grid (row, group, chunk), a grid step one chunk
  of all the heads that share a group's ``B`` and ``C`` (``C B'`` is formed
  once for them), their carried states in VMEM, the chunk axis
  ``arbitrary``, ``lengths`` prefetched; a chunk wholly past its row's
  length is neither computed nor fetched (its block index names the row's
  last live chunk again). The hand-over of the state from chunk to chunk is
  the kernel's scratch; the decays' running sums are XLA's (:func:`decays`).
- :func:`scan` picks by platform: the kernel on a TPU, the walk elsewhere.

The products read ``xbc``'s dtype (the served one) and accumulate in
float32; decays, running sums and the state are float32.

:func:`causal_conv` is the depthwise conv before the scan with the tail it
hands on (the ``taps - 1`` inputs before ``lengths[b]``); :func:`ssm_step`
one token a row against (state, conv tail).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANES = 128    # heads narrower than this share a lane block of the kernel


def causal_conv(x, w, bias, lengths):
    """Depthwise causal conv over the token axis: ``x`` [B, T, C] float32,
    ``w`` [taps, C], slot t sees t - taps + 1 .. t. Returns (y [B, T, C]
    float32, tail [B, taps - 1, C]: the inputs of slots ``lengths[b] -
    taps + 1 .. lengths[b] - 1``, zeros before the row's start)."""
    taps, t = w.shape[0], x.shape[1]
    padded = jnp.pad(x, ((0, 0), (taps - 1, 0), (0, 0)))
    w32 = w.astype(jnp.float32)
    y = bias.astype(jnp.float32) + sum(w32[k] * padded[:, k:k + t] for k in range(taps))
    at = lengths[:, None] + jnp.arange(taps - 1)[None, :]                 # padded slot l + k is input l - taps + 1 + k
    return y, jnp.take_along_axis(padded, at[:, :, None], axis=1)


def decays(dt, a, chunk: int):
    """``dt`` [B, T, H] float32 (zero at padding), ``a`` [H] -> (dt, cs) as
    [B, H, T / chunk, chunk]: cs the running sum of ``dt * a`` inside each chunk.
    ``a`` None: ``dt`` are the log-decays themselves (ops/retention.py's gates)."""
    b, t, h = dt.shape
    dt_c = dt.transpose(0, 2, 1).reshape(b, h, t // chunk, chunk)
    return dt_c, jnp.cumsum(dt_c if a is None else dt_c * a[None, :, None, None], axis=-1)


def _widths(xbc, heads: int, head_dim: int, groups: int):
    n = (xbc.shape[-1] - heads * head_dim) // (2 * groups)
    return heads * head_dim, groups * n, n


def chunked_scan(xbc, dt, a, *, heads: int, head_dim: int, groups: int, chunk: int, precision=None):
    """``xbc`` [B, T, H*P + 2*G*N] (x | B | C after the conv and its
    activation), ``dt`` [B, T, H] float32 with zeros at padding, ``a`` [H].
    ``T`` a multiple of ``chunk``. Returns (y [B, T, H*P] in ``xbc``'s dtype,
    state [B, H, P, N] float32 after the last slot)."""
    b, t, _ = xbc.shape
    ssm, bc, n = _widths(xbc, heads, head_dim, groups)
    nc, per, f32 = t // chunk, heads // groups, jnp.float32
    x = xbc[..., :ssm].reshape(b, nc, chunk, heads, head_dim)
    bm = xbc[..., ssm:ssm + bc].reshape(b, nc, chunk, groups, n)
    cm = xbc[..., ssm + bc:].reshape(b, nc, chunk, groups, n)
    dt_c, cs = decays(dt, a, chunk)                                        # [B, H, nc, Q]
    causal = jnp.tril(jnp.ones((chunk, chunk), bool))

    def one(state, inputs):
        x_c, b_c, c_c, dt_q, cs_q = inputs                                # [B,Q,H,P] [B,Q,G,N] [B,Q,G,N] [B,H,Q] [B,H,Q]
        cb = jnp.einsum("bign,bjgn->bgij", c_c, b_c, precision=precision, preferred_element_type=f32)
        decay = jnp.exp(jnp.where(causal, cs_q[..., :, None] - cs_q[..., None, :], -jnp.inf))   # [B,H,Q,Q]
        m = jnp.repeat(cb, per, axis=1) * decay * dt_q[..., None, :]
        y = jnp.einsum("bhij,bjhp->bihp", m.astype(x_c.dtype), x_c, precision=precision, preferred_element_type=f32)
        c_h = jnp.repeat(c_c, per, axis=2)                                # [B,Q,H,N]
        y += jnp.exp(cs_q).transpose(0, 2, 1)[..., None] * jnp.einsum(
            "bihn,bhpn->bihp", c_h, state.astype(x_c.dtype), precision=precision, preferred_element_type=f32)
        w = jnp.exp(cs_q[..., -1:] - cs_q) * dt_q                         # [B,H,Q]
        b_w = (jnp.repeat(b_c, per, axis=2).astype(f32) * w.transpose(0, 2, 1)[..., None]).astype(x_c.dtype)
        state = jnp.exp(cs_q[..., -1])[..., None, None] * state + jnp.einsum(
            "bjhp,bjhn->bhpn", x_c, b_w, precision=precision, preferred_element_type=f32)
        return state, y.astype(xbc.dtype)

    chunks_first = lambda z: jnp.moveaxis(z, 1, 0)
    state0 = jnp.zeros((b, heads, head_dim, n), f32)
    state, y = jax.lax.scan(one, state0, (chunks_first(x), chunks_first(bm), chunks_first(cm),
                                          jnp.moveaxis(dt_c, 2, 0), jnp.moveaxis(cs, 2, 0)))
    return jnp.moveaxis(y, 0, 1).reshape(b, t, ssm), state


# ------------------------------------------------------------ the Mosaic kernel

def _column(row, q: int):
    """[1, Q] -> [Q, 1] without a relayout: the diagonal of its broadcast."""
    eye = jax.lax.broadcasted_iota(jnp.int32, (q, q), 0) == jax.lax.broadcasted_iota(jnp.int32, (q, q), 1)
    return jnp.sum(jnp.where(eye, jnp.broadcast_to(row, (q, q)), 0.0), axis=1, keepdims=True)


def _scan_kernel(lens_ref, x_ref, b_ref, c_ref, dt_ref, cs_ref, y_ref, s_ref, state_ref, *,
                 chunk: int, per: int, head_dim: int, side: int):
    """One chunk of one group's ``per`` heads. The heads are walked ``side``
    at a time, side by side in one block of ``side * head_dim`` lanes (two
    heads of 64 fill the 128 lanes a matrix product wants): a product is
    formed for the whole block with one head's decays and kept at that
    head's lanes (rows, for the state)."""
    bi, ci = pl.program_id(0), pl.program_id(2)
    n_live = (lens_ref[bi] + chunk - 1) // chunk
    width, f32 = side * head_dim, jnp.float32

    @pl.when(ci == 0)
    def _():
        state_ref[...] = jnp.zeros_like(state_ref)

    @pl.when(ci < n_live)
    def _():
        bm, cm = b_ref[0], c_ref[0]                                       # [Q,N] [Q,N]
        n = bm.shape[-1]
        cb = jax.lax.dot_general(cm, bm, (((1,), (1,)), ((), ())), preferred_element_type=f32)   # [Q,Q]: once a group
        causal = (jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
                  <= jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0))
        lane_head = jax.lax.broadcasted_iota(jnp.int32, (chunk, width), 1) // head_dim          # of y's lanes
        row_head_q = jax.lax.broadcasted_iota(jnp.int32, (width, chunk), 0) // head_dim         # of x'ᵀ's rows
        row_head_n = jax.lax.broadcasted_iota(jnp.int32, (width, n), 0) // head_dim             # of the state's rows
        for s in range(per // side):
            x = x_ref[0, :, pl.ds(s * width, width)]                      # [Q, side*P]
            state = state_ref[s]                                          # [side*P, N]
            from_state = jax.lax.dot_general(cm, state.astype(x.dtype), (((1,), (1,)), ((), ())),
                                             preferred_element_type=f32)                        # [Q, side*P]
            y = into = keep = None
            for k in range(side):
                h = s * side + k
                dt_row, cs_row = dt_ref[0, h, pl.ds(ci, 1), :], cs_ref[0, h, pl.ds(ci, 1), :]   # [1,Q]
                cs_col = _column(cs_row, chunk)
                decay = jnp.exp(jnp.where(causal, cs_col - cs_row, -jnp.inf))
                y_k = jnp.dot((cb * decay * dt_row).astype(x.dtype), x, preferred_element_type=f32)
                y_k += jnp.exp(cs_col) * from_state
                cs_last = cs_row[:, chunk - 1:chunk]                      # [1,1]
                into_k = jnp.broadcast_to(jnp.exp(cs_last - cs_row) * dt_row, (width, chunk))
                keep_k = jnp.broadcast_to(jnp.exp(jnp.broadcast_to(cs_last, (1, n))), (width, n))   # along lanes first
                if k == 0:
                    y, into, keep = y_k, into_k, keep_k
                else:
                    y = jnp.where(lane_head == k, y_k, y)
                    into = jnp.where(row_head_q == k, into_k, into)
                    keep = jnp.where(row_head_n == k, keep_k, keep)
            y_ref[0, :, pl.ds(s * width, width)] = y.astype(y_ref.dtype)
            x_w = (x.T.astype(f32) * into).astype(x.dtype)                # [side*P, Q]
            state_ref[s] = keep * state + jnp.dot(x_w, bm, preferred_element_type=f32)

    @pl.when(ci >= n_live)
    def _():
        y_ref[...] = jnp.zeros_like(y_ref)

    @pl.when(ci == pl.num_programs(2) - 1)
    def _():
        s_ref[0] = state_ref[...]


def pallas_scan(xbc, dt, a, lengths, *, heads: int, head_dim: int, groups: int, chunk: int, interpret: bool = False):
    """:func:`chunked_scan` as the kernel ``ssd_prefill``; ``lengths`` [B]
    says which chunks of a row are live."""
    b, t, _ = xbc.shape
    ssm, bc, n = _widths(xbc, heads, head_dim, groups)
    nc, per = t // chunk, heads // groups
    side = max(1, min(per, LANES // head_dim))
    while per % side:
        side -= 1
    width = side * head_dim
    dt_c, cs = decays(dt, a, chunk)

    def live(bi, ci, lens):   # a chunk past the row's length names the last live one: nothing is fetched for it
        return jnp.minimum(ci, jnp.maximum((lens[bi] + chunk - 1) // chunk - 1, 0))

    # x, B and C are column blocks of the one array: group g's heads' x at block g of width per * P, its B and C
    # behind all the x's at blocks of width N
    assert ssm % n == 0 and bc % n == 0, "x | B | C must start on a state-width block"
    x_spec = pl.BlockSpec((1, chunk, per * head_dim), lambda bi, gi, ci, lens: (bi, live(bi, ci, lens), gi))
    b_spec = pl.BlockSpec((1, chunk, n), lambda bi, gi, ci, lens: (bi, live(bi, ci, lens), ssm // n + gi))
    c_spec = pl.BlockSpec((1, chunk, n), lambda bi, gi, ci, lens: (bi, live(bi, ci, lens), (ssm + bc) // n + gi))
    row_spec = pl.BlockSpec((1, per, nc, chunk), lambda bi, gi, ci, lens: (bi, gi, 0, 0))
    y, state = pl.pallas_call(
        functools.partial(_scan_kernel, chunk=chunk, per=per, head_dim=head_dim, side=side),
        name="ssd_prefill",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(b, groups, nc),
            in_specs=[x_spec, b_spec, c_spec, row_spec, row_spec],
            out_specs=[pl.BlockSpec((1, chunk, per * head_dim), lambda bi, gi, ci, lens: (bi, ci, gi)),
                       pl.BlockSpec((1, per // side, width, n), lambda bi, gi, ci, lens: (bi, gi, 0, 0))],
            scratch_shapes=[pltpu.VMEM((per // side, width, n), jnp.float32)]),
        out_shape=[jax.ShapeDtypeStruct((b, t, ssm), xbc.dtype),
                   jax.ShapeDtypeStruct((b, heads // side, width, n), jnp.float32)],
        compiler_params=pltpu.CompilerParams(dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(lengths.astype(jnp.int32), xbc, xbc, xbc, dt_c, cs)
    return y, state.reshape(b, heads, head_dim, n)      # the heads of a block lie one after the other: no copy


def scan(xbc, dt, a, lengths, *, heads: int, head_dim: int, groups: int, chunk: int):
    """The scan by platform: ``ssd_prefill`` on a TPU, the walk elsewhere."""
    kw = dict(heads=heads, head_dim=head_dim, groups=groups, chunk=chunk)
    if jax.default_backend() == "tpu":
        return pallas_scan(xbc, dt, a, lengths, **kw)
    return chunked_scan(xbc, dt, a, **kw)


def chunk_counts(lengths, slots: int, chunk: int):
    """(chunks dispatched, chunks wholly past their row's length) of one
    scan over ``lengths`` [B] in ``slots`` slots a row, float32."""
    nc = slots // chunk
    live = (lengths + chunk - 1) // chunk
    return jnp.float32(lengths.shape[0] * nc), (nc - live).sum().astype(jnp.float32)


def ssm_step(xbc_new, dt, a, state, tail, conv_w, conv_b, *, heads: int, head_dim: int, groups: int):
    """One token a row: ``xbc_new`` [B, C] float32 (before the conv), ``dt``
    [B, H] float32 (after its softplus), ``state`` [B, H, P, N] float32,
    ``tail`` [B, taps - 1, C] float32. Returns (y [B, H*P], x [B, H*P],
    state, tail), all float32: a step's arithmetic is a few megabytes of
    elementwise work."""
    f32 = jnp.float32
    seen = jnp.concatenate([tail, xbc_new[:, None, :]], axis=1)           # [B, taps, C]
    xbc = jax.nn.silu(conv_b.astype(f32) + jnp.sum(conv_w.astype(f32)[None] * seen, axis=1))
    ssm, bc, n = _widths(xbc, heads, head_dim, groups)
    per = heads // groups
    x = xbc[:, :ssm].reshape(-1, heads, head_dim)
    bm = jnp.repeat(xbc[:, ssm:ssm + bc].reshape(-1, groups, n), per, axis=1)
    cm = jnp.repeat(xbc[:, ssm + bc:].reshape(-1, groups, n), per, axis=1)
    state = jnp.exp(dt * a[None, :])[..., None, None] * state + (dt[..., None] * x)[..., None] * bm[:, :, None, :]
    y = jnp.sum(state * cm[:, :, None, :], axis=-1)
    return y.reshape(-1, ssm), x.reshape(-1, ssm), state, seen[:, 1:]
