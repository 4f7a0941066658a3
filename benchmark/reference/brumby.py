"""The plain reference of Brumby-14B-Base's language model behind a patch
embedding: ``jax.numpy``, float32, ``highest``; power retention in its
**attention form**, a block of queries at a time, so that it never forms a
feature map or a state (the program's chunked and recurrent forms are
neither); no cache, no kernel, no batching. It imports nothing of the
program. Papers: Gelada, Buckman, Zhang, Bhaskar, "Scaling Context Requires
Rethinking Attention", arXiv:2507.04239 (power attention, gating, the
chunked form); Manifest AI's Brumby-14B-Base release, 2025-10.

Every layer on ``x [T, D]`` (RMSNorm ``N`` with a learned gain, eps
``rms_norm_eps``)::

    n = N(x)
    q_h = rope(N_128(n W_q)_h, t),  k_j = rope(N_128(n W_k)_j, t),  v_j = (n W_v)_j
    log g_(t,j) = logsigmoid(n_t . W_g[:, j] + b_g[j]),  G the running sum of log g over t
    y_(t,h) = sum_(s<=t) e^(G_t - G_s) (q_(t,h) . k_(s,j))**2 v_(s,j)
              / (sum_(s<=t) e^(G_t - G_s) (q_(t,h) . k_(s,j))**2 + eps),   j = h // 5
    x <- x + concat(y) W_o;  x <- x + W_down (silu(W_gate n') * W_up n'),  n' = N(x)

``rope`` rotates the pairs (i, i + 64) of a head at ``rope_theta``; position
``t`` is a token's index in the image's patches, the answer's ids after them.
A final RMSNorm and the untied head follow. What the row does not say is
under ``assumed`` in the configuration's file.

Weights: every leaf from ``(seed, leaf name)`` (``leaves.py``), a leaf of
more than 16 M values in row blocks of its own streams
(``nemotron_h.blocks``), so that threads make one leaf together and the
check makes the rows of the embedding it needs. ``model.leaf_gain`` scales
the matrices.

``control`` names what must read not correct (:data:`CONTROLS`). A control
is computed in the **recurrent form** (:func:`recurrent`: the image's tokens
in chunks of 128, as the program's prefill, then one token a row through the
state each row hands on, with its own exact symmetric feature map of
``d (d + 1) / 2`` products), so that it answers the cell's own steps greedily
at no more cost than the program: a lower precision of the layers' matrices
(``int8``, ``fp8``); the steps from a zero state (``no_state_carry``: the
answer sees none of the image); the normalisation left out (``no_norm``: the
weighted sum of values, not their weighted mean); no decay (``no_gate``: g =
1); and ``state_bf16``, the program's own precision but for its state: the
matrices in bfloat16, as the configuration states them, and the state and
normaliser rounded to bfloat16 after each chunk and step, below the float32
it states; the check tells that one by ``logit_max`` (PERF.md, section 6).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.reference.longcat import patches  # noqa: F401  (the same stand-in tokens)
from benchmark.reference.nemotron_h import _low, blocks

HI = jax.lax.Precision.HIGHEST
CONTROLS = ("fp8", "int8", "no_state_carry", "no_norm", "no_gate", "state_bf16")
EPS = 1e-6            # the normaliser's floor (assumed)
QUERY_BLOCK = 256     # queries a block of the attention form
MATRICES = ("attn/w_q", "attn/w_k", "attn/w_v", "attn/w_o", "ffn/w_gate", "ffn/w_up", "ffn/w_down")   # what int8 and fp8 round
STATE_CHUNK = 128     # the image tokens a chunk of the recurrent form, as the program's prefill


# ------------------------------------------------------------------ leaves

def layer_leaves(m: dict) -> dict[str, tuple[int, ...]]:
    """One layer's leaves, named without their ``layer<l>/`` prefix."""
    d, hq, hk, dh, f = (m["hidden_size"], m["num_attention_heads"], m["num_key_value_heads"], m["head_dim"],
                        m["intermediate_size"])
    return {"norm": (d,), "attn/w_q": (d, hq * dh), "attn/w_k": (d, hk * dh), "attn/w_v": (d, hk * dh),
            "attn/q_norm": (dh,), "attn/k_norm": (dh,), "attn/w_g": (d, hk), "attn/b_g": (hk,),
            "attn/w_o": (hq * dh, d), "ffn_norm": (d,), "ffn/w_gate": (d, f), "ffn/w_up": (d, f),
            "ffn/w_down": (f, d)}


def outer_leaves(m: dict) -> dict[str, tuple[int, ...]]:
    d = m["hidden_size"]
    return {"embed/patch": (m["patch"] ** 2 * 3, d), "embed/token": (m["vocab_size"], d),
            "final_norm": (d,), "head": (d, m["vocab_size"])}


def all_leaves(m: dict) -> dict[str, tuple[int, ...]]:
    out = dict(outer_leaves(m))
    for l in range(m["num_hidden_layers"]):
        out |= {f"layer{l}/{k}": v for k, v in layer_leaves(m).items()}
    return out


def std(name: str, shape: tuple[int, ...], m: dict) -> float:
    """A matrix's deviation: ``leaf_gain[kind] / sqrt(fan in)``, the kind the
    leaf's last name; a token's embedding has no fan in."""
    kind = name.rsplit("/", 1)[-1]
    gain = m.get("leaf_gain", {}).get(kind, 1.0)
    return gain if kind == "token" else gain / float(np.sqrt(shape[0]))


def make_block(seed: int, name: str, shape: tuple[int, ...], m: dict, block: int) -> np.ndarray:
    """Rows ``blocks(shape)[block]`` of leaf ``name``, float32."""
    from benchmark.reference import leaves

    ranges = blocks(shape)
    if len(ranges) == 1:
        return make_leaf(seed, name, shape, m)
    r0, r1 = ranges[block]
    return leaves.normal(seed, f"{name}#{block}", (r1 - r0, *shape[1:]), std(name, shape, m))


def make_leaf(seed: int, name: str, shape: tuple[int, ...], m: dict) -> np.ndarray:
    """Leaf ``name`` in float32. A gain (``*norm``) is 1 + 0.1 z; the gate's
    bias ``b_g`` is ``log(M - 1)`` with a memory ``M = 1 / (1 - g)`` drawn
    log-uniform over ``gate_memory`` tokens; every other leaf ``std * z``."""
    from benchmark.reference import leaves

    kind = name.rsplit("/", 1)[-1]
    if kind.endswith("norm"):
        return 1.0 + leaves.normal(seed, name, shape, 0.1)
    if kind == "b_g":
        lo, hi = m.get("gate_memory", (64, 4096))
        memory = np.exp(leaves.generator(seed, name).uniform(np.log(lo), np.log(hi), shape))
        return np.log(memory - 1.0).astype(np.float32)
    if len(blocks(shape)) > 1:
        return np.concatenate([make_block(seed, name, shape, m, b) for b in range(len(blocks(shape)))])
    return leaves.normal(seed, name, shape, std(name, shape, m))


# ------------------------------------------------------------------ the walk

def _bf16(a):
    """``a`` rounded to bfloat16's precision, in float32: ``reduce_precision``,
    which XLA keeps, where a cast there and back may be dropped as excess
    precision (on the chip it was: PERF.md, section 6)."""
    return jax.lax.reduce_precision(a, exponent_bits=8, mantissa_bits=7)


def _dense(x, w, control=None):
    """A layer's matrix product; under ``fp8`` both operands are rounded, under
    ``int8`` and ``state_bf16`` the matrix."""
    w = _bf16(w) if control == "state_bf16" else _low(w, control)
    return jnp.matmul(_low(x, "fp8") if control == "fp8" else x, w, precision=HI)


def _norm(x, gain, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * gain


def rope(x, theta: float, pos=None):
    """``x [T, heads, d]`` rotated at positions ``pos [T]`` (0..T-1 if None),
    the pairs (i, i + d/2)."""
    t, d = x.shape[0], x.shape[-1]
    pos = jnp.arange(t, dtype=jnp.float32) if pos is None else jnp.asarray(pos, jnp.float32)
    ang = pos[:, None] / theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    x1, x2 = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def projections(m: dict, w: dict, n, control=None, pos=None):
    """q [T, Hq, d], k and v [T, Hk, d], log g [T, Hk] of ``n [T, D]`` at positions ``pos``."""
    t, hq, hk, dh, eps = n.shape[0], m["num_attention_heads"], m["num_key_value_heads"], m["head_dim"], m["rms_norm_eps"]
    q = rope(_norm(_dense(n, w["attn/w_q"], control).reshape(t, hq, dh), w["attn/q_norm"], eps), m["rope_theta"], pos)
    k = rope(_norm(_dense(n, w["attn/w_k"], control).reshape(t, hk, dh), w["attn/k_norm"], eps), m["rope_theta"], pos)
    v = _dense(n, w["attn/w_v"], control).reshape(t, hk, dh)
    log_g = jax.nn.log_sigmoid(jnp.matmul(n, w["attn/w_g"], precision=HI) + w["attn/b_g"])
    return q, k, v, (jnp.zeros_like(log_g) if control == "no_gate" else log_g)


def attention_form(q, k, v, log_g):
    """y [T, Hq, d]: each query's weighted mean of the values before it, the
    weights ``e^(G_t - G_s) (q . k)**2``, a block of queries at a time."""
    t, hq, dh = q.shape
    hk = k.shape[1]
    per = hq // hk
    big = jnp.cumsum(log_g, axis=0)                                         # [T, Hk]
    k_q, v_q = jnp.repeat(k, per, axis=1), jnp.repeat(v, per, axis=1)       # a query head's key and value head
    g_q = jnp.repeat(big, per, axis=1)                                      # [T, Hq]
    n_blocks = -(-t // QUERY_BLOCK)
    q_pad = jnp.pad(q, ((0, n_blocks * QUERY_BLOCK - t), (0, 0), (0, 0)))
    cols = jnp.arange(t)

    def one(i):
        rows = i * QUERY_BLOCK + jnp.arange(QUERY_BLOCK)
        q_b = jax.lax.dynamic_slice_in_dim(q_pad, i * QUERY_BLOCK, QUERY_BLOCK)
        g_b = jnp.take(g_q, jnp.minimum(rows, t - 1), axis=0)               # [Qb, Hq]
        seen = cols[None, :] <= rows[:, None]
        scores = jnp.einsum("qhd,khd->hqk", q_b, k_q, precision=HI) ** 2
        decay = jnp.exp(jnp.where(seen[None], g_b.T[:, :, None] - g_q.T[:, None, :], -jnp.inf))
        p = scores * decay
        num = jnp.einsum("hqk,khd->qhd", p, v_q, precision=HI)
        return num / (p.sum(-1).T[..., None] + EPS)

    return jax.lax.map(one, jnp.arange(n_blocks)).reshape(-1, hq, dh)[:t]


def features(u):
    """The exact symmetric degree-2 map of ``u [..., d]``: ``u_a u_b`` for
    ``a <= b``, times sqrt 2 off the diagonal: ``phi(q) . phi(k) = (q . k)**2``."""
    a, b = np.triu_indices(u.shape[-1])
    return u[..., a] * u[..., b] * jnp.asarray(np.where(a == b, 1.0, np.sqrt(2.0)), jnp.float32)


def zero_state(k):
    """The state before a sequence's first token, for keys ``k [..., Hk, d]``:
    ``S [Hk, D, d]`` and ``z [Hk, D]``."""
    hk, dh = k.shape[-2:]
    n_feat = dh * (dh + 1) // 2
    return jnp.zeros((hk, n_feat, dh), jnp.float32), jnp.zeros((hk, n_feat), jnp.float32)


def state_form(q, k, v, log_g, s, z, chunk: int, control=None):
    """The recurrent form of one sequence from the state ``s [Hk, D, d]``,
    ``z [Hk, D]``: ``q [T, Hq, d]``, ``k`` and ``v`` [T, Hk, d], ``log_g [T,
    Hk]``, ``T`` a multiple of ``chunk``, a chunk at a time (its own tokens'
    part as in the attention form, the earlier ones' through the state).
    Returns (y [T, Hq, d], s, z) after the last token; a token with k = 0 and
    log g = 0 leaves the state as it was. Under ``state_bf16`` the state is
    rounded to bfloat16 after each chunk; under
    ``no_norm`` y is the weighted sum."""
    t, hq, dh = q.shape
    hk = k.shape[1]
    per = hq // hk
    keep = _bf16 if control == "state_bf16" else (lambda a: a)
    causal = jnp.tril(jnp.ones((chunk, chunk), bool))

    def one(carry, chunk_of):
        s, z = carry
        qs, ks, vs, lg = chunk_of
        cs = jnp.cumsum(lg, axis=0)                                         # [c, Hk]
        qg = qs.reshape(chunk, hk, per, dh)
        p = jnp.einsum("tjrd,sjd->jrts", qg, ks, precision=HI) ** 2
        p = p * jnp.exp(jnp.where(causal, cs.T[:, :, None] - cs.T[:, None, :], -jnp.inf))[:, None]
        phi_q = features(qg)                                                # [c, Hk, R, D]
        e = jnp.exp(cs)[:, :, None]                                         # [c, Hk, 1]
        num = jnp.einsum("jrts,sjd->tjrd", p, vs, precision=HI) + e[..., None] * jnp.einsum(
            "tjrf,jfd->tjrd", phi_q, s, precision=HI)
        den = p.sum(-1).transpose(2, 0, 1) + e * jnp.einsum("tjrf,jf->tjr", phi_q, z, precision=HI)
        y = num if control == "no_norm" else num / (den[..., None] + EPS)
        phi_k = features(ks) * jnp.exp(cs[-1:] - cs)[..., None]             # [c, Hk, D]
        s = keep(jnp.exp(cs[-1])[:, None, None] * s + jnp.einsum("sjf,sjd->jfd", phi_k, vs, precision=HI))
        z = keep(jnp.exp(cs[-1])[:, None] * z + phi_k.sum(0))
        return (s, z), y.reshape(chunk, hq, dh)

    split = lambda a: a.reshape(t // chunk, chunk, *a.shape[1:])
    (s, z), y = jax.lax.scan(one, (s, z), tuple(map(split, (q, k, v, log_g))))
    return y.reshape(t, hq, dh), s, z


def _rest(m: dict, w: dict, x, y, control=None):
    """A layer after its retention: ``y`` through W_o onto ``x``, then the SwiGLU FFN."""
    x = x + _dense(y.reshape(x.shape[0], -1), w["attn/w_o"], control)
    n = _norm(x, w["ffn_norm"], m["rms_norm_eps"])
    hidden = jax.nn.silu(_dense(n, w["ffn/w_gate"], control)) * _dense(n, w["ffn/w_up"], control)
    return x + _dense(hidden, w["ffn/w_down"], control)


def layer(m: dict, w: dict, x, control: str | None = None):
    """One layer on ``x [T, D]`` in the attention form; ``w``: the layer's
    leaves; ``control`` None, or ``int8`` weights."""
    n = _norm(x, w["norm"], m["rms_norm_eps"])
    q, k, v, log_g = projections(m, w, n, control)
    return _rest(m, w, x, attention_form(q, k, v, log_g), control)


def prefill_layer(m: dict, w: dict, x, n_real, control=None):
    """A control's layer over one image's tokens ``x [T, D]`` (``T`` a
    multiple of :data:`STATE_CHUNK`, the first ``n_real`` real, the rest
    moving nothing) in the recurrent form: x after the layer, and the state
    that the image's last token hands on (none under ``no_state_carry``)."""
    n = _norm(x, w["norm"], m["rms_norm_eps"])
    q, k, v, log_g = projections(m, w, n, control)
    real = jnp.arange(x.shape[0]) < n_real
    k, log_g = jnp.where(real[:, None, None], k, 0.0), jnp.where(real[:, None], log_g, 0.0)
    y, s, z = state_form(q, k, v, log_g, *zero_state(k), STATE_CHUNK, control)
    return _rest(m, w, x, y, control), (zero_state(k) if control == "no_state_carry" else (s, z))


def step_layer(m: dict, w: dict, x, pos, state, control=None):
    """A control's layer over one token a row, ``x [N, D]`` at positions
    ``pos [N]``, through the rows' states (``s [N, Hk, D, d]``, ``z [N, Hk,
    D]``): x after the layer, and the states it leaves."""
    n = _norm(x, w["norm"], m["rms_norm_eps"])
    q, k, v, log_g = projections(m, w, n, control, pos)
    one = lambda q, k, v, g, s, z: state_form(q[None], k[None], v[None], g[None], s, z, 1, control)
    y, s, z = jax.vmap(one)(q, k, v, log_g, *state)
    return _rest(m, w, x, y[:, 0], control), (s, z)


def recurrent(m: dict, layer_of, head: dict, xs: list, steps: int, rows_after, control=None) -> None:
    """A control's model over images at once, in the recurrent form: each
    image's embedded tokens ``xs [T_i, D]`` through the layers a chunk at a
    time, then ``steps - 1`` tokens a row through the states the images hand
    on. ``layer_of(l)`` gives layer ``l``'s leaves (asked once for the
    prefill, a layer at a time, and once for the steps, all at once);
    ``rows_after(probs)`` takes each step's distributions ``[N, vocab]`` and
    gives the next step's token rows ``[N, D]`` (after the last step it is
    not asked)."""
    prefill = jax.jit(lambda w, x, n: prefill_layer(m, w, x, n, control))
    step = jax.jit(lambda w, x, pos, state: step_layer(m, w, x, pos, state, control), donate_argnums=(3,))
    probs = jax.jit(lambda h, x: head_probs(m, h, x))
    lengths = [len(x) for x in xs]
    xs = [jnp.pad(x, ((0, -len(x) % STATE_CHUNK), (0, 0))) for x in xs]
    states = []
    for l in range(m["num_hidden_layers"]):
        w = layer_of(l)
        outs = [prefill(w, x, n) for x, n in zip(xs, lengths)]
        xs = [o[0] for o in outs]
        states.append(jax.tree.map(lambda *a: jnp.stack(a), *(o[1] for o in outs)))
        del w, outs
    x = jnp.stack([x[n - 1] for x, n in zip(xs, lengths)])
    del xs
    layers = [layer_of(l) for l in range(m["num_hidden_layers"])]
    pos = jnp.asarray(lengths)
    for k in range(steps):
        rows = rows_after(probs(head, x))
        if k == steps - 1:
            break
        x = jnp.asarray(rows)
        for l, w in enumerate(layers):
            x, states[l] = step(w, x, pos + k, states[l])


def embed(w_patch, tokens: np.ndarray, id_rows) -> jnp.ndarray:
    """The patch tokens through the stand-in embedding, then the answer ids'
    rows of the token embedding (``id_rows [n, D]``, already looked up)."""
    x = jnp.matmul(jnp.asarray(tokens), w_patch, precision=HI)
    return jnp.concatenate([x, jnp.asarray(id_rows)]) if len(id_rows) else x


def head_probs(m: dict, w: dict, rows) -> jnp.ndarray:
    """The distributions after the given rows of the last layer's output."""
    logits = jnp.matmul(_norm(rows, w["final_norm"], m["rms_norm_eps"]), w["head"], precision=HI)
    return jax.nn.softmax(logits, axis=-1)


def forward(m: dict, w: dict, tokens: np.ndarray, ids, steps: int, control: str | None = None) -> np.ndarray:
    """The whole model on one image (``w``: every leaf, by its full name):
    the distributions of the last ``steps`` positions, [steps, vocab]; with
    ``control`` as :func:`layer` takes it. For the tests and small sizes; the
    check child walks layer by layer."""
    x = embed(w["embed/patch"], tokens, w["embed/token"][jnp.asarray(ids, jnp.int32)] if len(ids) else ())
    for l in range(m["num_hidden_layers"]):
        pre = f"layer{l}/"
        x = layer(m, {k[len(pre):]: v for k, v in w.items() if k.startswith(pre)}, x, control)
    return np.asarray(head_probs(m, w, x[-steps:]))
