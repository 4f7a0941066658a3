"""A routed expert layer that is told which experts it holds.

The router scores every expert of the published layer, routed and zero
ones alike; the layer computes what *its own* experts add
for the tokens routed to them, and what the zero (identity) experts add,
which every holder of a share computes alike. What the absent experts
would add is left out: that is the other chips' part, and no code stands in
for them.

Two routers (``expert_layer(router=...)``): :func:`route`, a softmax whose
``topk`` largest are kept as they are, and :func:`route_sigmoid`, sigmoid
scores chosen with a selection bias that the weights do not carry,
renormalised over the picks. Two expert shapes, told by the matrices the
layer is given: three (:func:`swiglu`) or two (:func:`relu2`, a squared
ReLU between them). Grouping, windows and the kernel serve both.

Two forms of the held experts' sum:

- ``dense``: every held expert over every token, weighted by the routing
  weight (zero where the token did not pick it). ``E_held`` times the
  necessary work: the definition, for the CPU and the tests.
- ``grouped``: the (token, expert) pairs sorted by expert, each expert's
  group padded to whole row tiles, and a grouped matrix product a matrix of
  the expert (``expert_gmm``, a Mosaic kernel: one expert's weights a row tile, chosen
  by a prefetched table) over a window of ``CHUNK`` rows at a time, as many
  windows as the pairs need. No pair is dropped and there is no capacity
  factor: a batch that routes more pairs here takes more windows.

Counters come back beside the result (:func:`expert_layer`): how many picks
the real tokens made, how many went to zero experts and to held ones, and
the busiest held expert's load.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

ROW_TILE = 128     # rows of one grid step of expert_gmm; a group is padded to whole tiles
CHUNK = 4096       # rows of one window of the grouped form


def route(u, w_router, topk: int, scale: float):
    """Softmax over all experts in float32, the ``topk`` largest kept, each
    weighted ``scale * p`` (not renormalised). Returns (weights [T, k]
    float32, ids [T, k] int32)."""
    logits = jnp.dot(u.astype(jnp.float32), w_router.astype(jnp.float32),
                     precision=jax.lax.Precision.HIGHEST)
    p = jax.nn.softmax(logits, axis=-1)
    top_p, ids = jax.lax.top_k(p, topk)
    return scale * top_p, ids.astype(jnp.int32)


def route_sigmoid(u, w_router, bias, topk: int, scale: float):
    """Sigmoid scores in float32 over all experts; the ``topk`` largest of
    ``score + bias`` are chosen (``bias`` [E] or None: it moves the choice
    and not the weight), each weighted ``scale * score / sum of the picked
    scores``. Returns (weights [T, k] float32, ids [T, k] int32)."""
    logits = jnp.dot(u.astype(jnp.float32), w_router.astype(jnp.float32),
                     precision=jax.lax.Precision.HIGHEST)
    s = jax.nn.sigmoid(logits)
    _, ids = jax.lax.top_k(s if bias is None else s + bias.astype(jnp.float32), topk)
    picked = jnp.take_along_axis(s, ids, axis=-1)
    return scale * picked / jnp.sum(picked, axis=-1, keepdims=True), ids.astype(jnp.int32)


def swiglu(x, w_gate, w_up, w_down):
    """SwiGLU with float32 accumulation; the two hidden products are kept
    in ``x``'s dtype (at 16,384 tokens x 12,288 a float32 pair is 1.6 GB)."""
    f32 = jnp.float32
    g = jnp.dot(x, w_gate, preferred_element_type=f32).astype(x.dtype)
    a = jnp.dot(x, w_up, preferred_element_type=f32).astype(x.dtype)
    hidden = (jax.nn.silu(g.astype(f32)) * a.astype(f32)).astype(x.dtype)
    return jnp.dot(hidden, w_down, preferred_element_type=f32)


def _relu2_hidden(a):
    return jnp.square(jax.nn.relu(a.astype(jnp.float32)))


def relu2(x, w_up, w_down):
    """``w_down (relu(w_up x))**2`` with float32 accumulation, the hidden
    product kept in ``x``'s dtype as :func:`swiglu`'s are."""
    f32 = jnp.float32
    a = jnp.dot(x, w_up, preferred_element_type=f32).astype(x.dtype)
    return jnp.dot(_relu2_hidden(a).astype(x.dtype), w_down, preferred_element_type=f32)


def expert_fn(n_matrices: int):
    """An expert of three matrices is a SwiGLU, one of two a squared ReLU."""
    return {3: swiglu, 2: relu2}[n_matrices]


def held_weights(weights, ids, held_first: int, n_held: int):
    """[T, n_held]: each token's routing weight on each held expert (ids
    ``held_first`` .. ``held_first + n_held - 1``), zero where not picked."""
    local = ids - held_first
    hit = (local[:, :, None] == jnp.arange(n_held)[None, None, :])
    return jnp.sum(jnp.where(hit, weights[:, :, None], 0.0), axis=1)


def _dense_sum(u, hw, *w):
    """``w``: the held experts' stacks, [E_held, ...] each (gate, up, down or up, down)."""
    expert = expert_fn(len(w))

    def one(acc, ws):
        e_w, *mats = ws
        return acc + e_w[:, None] * expert(u, *mats), None

    acc0 = jnp.zeros(u.shape, jnp.float32)
    acc, _ = jax.lax.scan(one, acc0, (hw.T, *w))
    return acc


# ------------------------------------------------------------ the grouped form

def _gmm_kernel(tile_expert_ref, n_tiles_ref, x_ref, w_ref, o_ref):
    i = pl.program_id(1)

    @pl.when(i < n_tiles_ref[0])
    def _():
        o_ref[...] = jnp.dot(x_ref[...], w_ref[0], preferred_element_type=jnp.float32).astype(o_ref.dtype)

    @pl.when(i >= n_tiles_ref[0])
    def _():
        o_ref[...] = jnp.zeros_like(o_ref)


def _col_tile(n: int, want: int) -> int:
    """The column tile for ``n`` columns: ``want`` where it divides them,
    else the largest multiple of 128 below it that does (2,688 and 1,920
    columns go in tiles of 384 where 512 were asked), else all of them in one."""
    if n <= want or n % want == 0:
        return min(n, want)
    return next((c for c in range(want - want % 128, 0, -128) if n % c == 0), n)


def expert_gmm(x, w, tile_expert, n_tiles, *, col_tile: int = 256, interpret: bool = False):
    """Rows ``x`` [R, K] in tiles of ``ROW_TILE``, tile ``i`` times expert
    ``tile_expert[i]``'s matrix of ``w`` [E, K, N]; tiles from ``n_tiles`` on
    are written as zeros and cost nothing. The column tile is the outer
    grid axis, so consecutive tiles of one expert reuse its block of weights."""
    r, k = x.shape
    n = w.shape[-1]
    col_tile = _col_tile(n, col_tile)
    assert r % ROW_TILE == 0 and n % col_tile == 0, (r, n, col_tile)

    def live(i, n_tiles):   # a dead tile names the last live one's blocks: nothing new is fetched for it
        return jnp.minimum(i, jnp.maximum(n_tiles[0] - 1, 0))

    return pl.pallas_call(
        _gmm_kernel, name="expert_gmm",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=(n // col_tile, r // ROW_TILE),
            in_specs=[pl.BlockSpec((ROW_TILE, k), lambda j, i, te, nt: (live(i, nt), 0)),
                      pl.BlockSpec((1, k, col_tile), lambda j, i, te, nt: (te[live(i, nt)], 0, j))],
            out_specs=pl.BlockSpec((ROW_TILE, col_tile), lambda j, i, te, nt: (i, j))),
        out_shape=jax.ShapeDtypeStruct((r, n), x.dtype),
        compiler_params=pltpu.CompilerParams(dimension_semantics=("parallel", "arbitrary"),
                                             vmem_limit_bytes=48 << 20),
        interpret=interpret,
    )(tile_expert, n_tiles, x, w)


def window_rows(t: int, k: int, n_held: int) -> int:
    """Rows of one window for ``t`` tokens: ``CHUNK``, or the worst case
    itself where that is smaller (a decode step's few tokens)."""
    worst = t * min(k, n_held) + n_held * ROW_TILE
    return min(CHUNK, -(-worst // ROW_TILE) * ROW_TILE)


def group_rows(weights, ids, held_first: int, n_held: int):
    """The (token, held expert) picks sorted by expert, each expert's group
    starting on a row tile. Returns (row_token [R], with ``T`` where a row
    is padding, row_weight [R], tile_expert [R / ROW_TILE], rows used).
    ``R`` covers the worst case, every pick of every token held here: these
    are index arrays, a few bytes a pick; the activations are gathered a
    window at a time."""
    t, k = ids.shape
    local = (ids - held_first).ravel()
    held = (local >= 0) & (local < n_held) & (weights.ravel() > 0)
    key = jnp.where(held, local, n_held)                                    # the picks not held sort last
    tokens = jnp.repeat(jnp.arange(t, dtype=jnp.int32), k)
    key, tokens, wts = jax.lax.sort((key, tokens, weights.ravel()), num_keys=1, is_stable=True)
    counts = jnp.sum(key[None, :] == jnp.arange(n_held)[:, None], axis=1).astype(jnp.int32)
    padded = -(-counts // ROW_TILE) * ROW_TILE
    ends = jnp.cumsum(padded)
    starts, first_pick = ends - padded, jnp.cumsum(counts) - counts
    chunk = window_rows(t, k, n_held)
    r_max = -(-(t * min(k, n_held) + n_held * ROW_TILE) // chunk) * chunk
    row = jnp.arange(r_max, dtype=jnp.int32)
    tile_expert = jnp.minimum(jnp.searchsorted(ends, row[::ROW_TILE], side="right"), n_held - 1).astype(jnp.int32)
    e = jnp.repeat(tile_expert, ROW_TILE)
    rank = row - starts[e]
    live = (rank < counts[e]) & (row < ends[-1])
    pick = jnp.where(live, first_pick[e] + rank, 0)
    return jnp.where(live, tokens[pick], t), jnp.where(live, wts[pick], 0.0), tile_expert, ends[-1]


def _grouped_sum(u, weights, ids, held_first, *w, interpret: bool = False):
    """``w``: the held experts' stacks, as :func:`_dense_sum` takes them."""
    t, d = u.shape
    *w_in, w_down = w
    n_held = w_down.shape[0]
    row_token, row_weight, tile_expert, rows_used = group_rows(weights, ids, held_first, n_held)
    u_pad = jnp.concatenate([u, jnp.zeros((1, d), u.dtype)])               # row T: what padding gathers
    chunk = window_rows(t, ids.shape[1], n_held)
    tiles = chunk // ROW_TILE

    def window(state):
        c, acc = state
        r0 = c * chunk
        rows = jax.lax.dynamic_slice_in_dim(row_token, r0, chunk)
        wts = jax.lax.dynamic_slice_in_dim(row_weight, r0, chunk)
        te = jax.lax.dynamic_slice_in_dim(tile_expert, c * tiles, tiles)
        n_tiles = jnp.clip((rows_used - r0 + ROW_TILE - 1) // ROW_TILE, 0, tiles).astype(jnp.int32)[None]
        x = u_pad[rows]
        gmm = functools.partial(expert_gmm, tile_expert=te, n_tiles=n_tiles, interpret=interpret)
        if len(w_in) == 2:
            g, a = (gmm(x, m) for m in w_in)
            hidden = (jax.nn.silu(g.astype(jnp.float32)) * a.astype(jnp.float32)).astype(u.dtype)
        else:
            hidden = _relu2_hidden(gmm(x, w_in[0], col_tile=512)).astype(u.dtype)
        y = gmm(hidden, w_down, col_tile=512).astype(jnp.float32) * wts[:, None]
        return c + 1, acc.at[rows].add(y, mode="drop")

    n_windows = (rows_used + chunk - 1) // chunk
    _, acc = jax.lax.while_loop(lambda s: s[0] < n_windows, window,
                                (jnp.int32(0), jnp.zeros((t, d), jnp.float32)))
    return acc


def expert_layer(u, valid, w_router, *w, topk: int, scale: float, n_routed: int, held_first: int = 0,
                 router: str = "softmax", router_bias=None):
    """``m`` [T, D] float32 for the tokens ``u`` [T, D] (``valid`` [T] bool:
    padding is routed nowhere and adds nothing), and the layer's counters.

    ``w``: the held experts, ids ``held_first`` onward, as stacks:
    ``w_gate``/``w_up`` [E_held, D, F] and ``w_down`` [E_held, F, D]
    (SwiGLU), or ``w_up`` and ``w_down`` alone (squared ReLU). ``router``:
    ``"softmax"`` (:func:`route`) or ``"sigmoid"`` (:func:`route_sigmoid`,
    with ``router_bias``). Ids from ``n_routed`` on are zero experts,
    ``E(u) = u``; a router no wider than ``n_routed`` has none."""
    n_held = w[-1].shape[0]
    with jax.named_scope("router"):
        if router == "sigmoid":
            weights, ids = route_sigmoid(u, w_router, router_bias, topk, scale)
        else:
            weights, ids = route(u, w_router, topk, scale)
        weights = jnp.where(valid[:, None], weights, 0.0)
        has_zero = w_router.shape[-1] > n_routed
        zero_w = jnp.sum(jnp.where(ids >= n_routed, weights, 0.0), axis=1) if has_zero else None
        hw = held_weights(weights, ids, held_first, n_held)
    with jax.named_scope("experts"):
        if jax.default_backend() == "tpu":
            m = _grouped_sum(u, weights, ids, held_first, *w)
        else:
            m = _dense_sum(u, hw, *w)
        if has_zero:
            m = m + zero_w[:, None] * u.astype(jnp.float32)
    real = valid[:, None]
    load = (hw > 0).sum(axis=0).astype(jnp.float32)                         # tokens a held expert
    counters = {
        "picks": valid.sum() * ids.shape[1], "zero_picks": (real & (ids >= n_routed)).sum(),
        "held_picks": (real & (ids >= held_first) & (ids < held_first + n_held)).sum(),
        "held_expert_load_max": load.max(), "held_expert_load_mean": load.mean(),
    }
    return m, {k: v.astype(jnp.float32) for k, v in counters.items()}
