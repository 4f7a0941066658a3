"""The plain reference of Nemotron-H's language model (Nemotron 3 Nano)
behind a patch embedding: ``jax.numpy``, float32, ``highest``; no cache, no
kernel, no chunks, no batching: the state-space recurrence token by token
(``lax.scan``), the full T x T softmax an image, the experts a dense sum
over the held ids. It imports nothing of the program.

Every layer is ``x <- x + mixer(N(x))`` on ``x [T, D]`` (RMSNorm ``N`` with a
learned gain, eps ``layer_norm_epsilon``), its kind a character of
``hybrid_override_pattern``; a final RMSNorm and the head follow.

- ``M``, Mamba-2: ``[z | xBC | dt] = W_in n`` (4096 | 6144 | 64 at the
  published sizes, no bias); ``xBC <- silu(conv(xBC))``, a causal depthwise
  convolution over the last ``conv_kernel`` positions with bias; ``xBC`` is
  ``u`` (heads x head_dim) | ``B`` | ``C`` (``n_groups`` x state each, a
  group serves heads / groups heads); ``delta = softplus(dt + dt_bias)``,
  ``a = exp(-delta exp(A_log))``; per head ``h_t = a_t h_(t-1) + delta_t u_t
  (x) B_t`` ([head_dim, state], ``h_(-1) = 0``), ``y_t = h_t C_t + D u_t``;
  then the gate and a group RMSNorm with a gain, ``W_out N_g(y * silu(z))``.
- ``*``, attention: ``q = W_q n`` (32 heads of 128), ``k, v = W_k n, W_v n``
  (2 heads of 128), no bias, **no positional encoding**, causal softmax of
  ``q k' / sqrt(128)``, query head i reads key/value head ``i // 16``, ``W_o``.
- ``E``, experts: ``s = sigmoid(W_r n)`` over all ``n_routed_experts``; the
  ``num_experts_per_tok`` largest of ``s + b`` (``b`` the selection bias);
  ``w_i = routed_scaling_factor * s_i / sum over the picked s``; an expert is
  ``W_down relu(W_up n)**2``; the shared expert the same, wider, always on.
  Only the experts held here (``experts_held`` from ``experts_held_first``)
  add: the absent ones are the other chip's part, in the program and here
  alike; the shared expert is computed by every chip of the pair.

What the row does not say is under ``assumed`` in the configuration's file.

Weights: every leaf from ``(seed, leaf name)`` (``leaves.py``), a leaf of
more than 16 M values in row blocks of its own streams (``name#block``), so
that threads make one leaf together and the check makes the rows of the
embedding it needs. ``dt_bias``, ``a_log`` and ``d`` are drawn as the family
initialises them; ``model.leaf_gain`` scales the matrices so that each kind
of layer adds its part to the residual stream.

An answer is ``answer_steps`` greedy steps. Everything here is causal, so one
forward over an image's tokens and the ids the steps before put first reads
every step at once.

``control`` names what must read not correct (:data:`CONTROLS`): a lower
precision of the layers' matrices (``int8``, ``fp8``); the answer steps from
a zero recurrent state (``no_state_carry``) or a zero conv tail
(``no_conv_tail``), the faults of a server that hands neither on from the
prefill (step 1 cannot see them: it is the prefill's own); a part left out
(``no_shared_expert``, ``no_held_experts``); the picks' weights not
renormalised (``no_topk_norm``).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.reference.longcat import patches  # noqa: F401  (the same stand-in tokens)

HI = jax.lax.Precision.HIGHEST
CONTROLS = ("fp8", "int8", "no_state_carry", "no_conv_tail", "no_shared_expert", "no_held_experts", "no_topk_norm")
BLOCK_VALUES = 1 << 24      # a leaf of more values is drawn in row blocks of at most this many


# ------------------------------------------------------------------ leaves

def widths(m: dict) -> dict[str, int]:
    """The widths the layers' shapes are made of."""
    inner, bc = m["mamba_num_heads"] * m["mamba_head_dim"], m["n_groups"] * m["ssm_state_size"]
    return {"inner": inner, "bc": bc, "conv": inner + 2 * bc, "in": 2 * inner + 2 * bc + m["mamba_num_heads"],
            "q": m["num_attention_heads"] * m["head_dim"], "kv": m["num_key_value_heads"] * m["head_dim"]}


def held_ids(m: dict) -> range:
    first = m.get("experts_held_first", 0)
    return range(first, first + m["experts_held"])


def layer_leaves(m: dict, kind: str) -> dict[str, tuple[int, ...]]:
    """One layer's leaves by its kind, named without their ``layer<l>/`` prefix."""
    d, w, heads = m["hidden_size"], widths(m), m["mamba_num_heads"]
    if kind == "M":
        return {"norm": (d,), "mixer/w_in": (d, w["in"]), "mixer/conv_w": (m["conv_kernel"], w["conv"]),
                "mixer/conv_b": (w["conv"],), "mixer/dt_bias": (heads,), "mixer/a_log": (heads,), "mixer/d": (heads,),
                "mixer/norm": (w["inner"],), "mixer/w_out": (w["inner"], d)}
    if kind == "*":
        return {"norm": (d,), "attn/w_q": (d, w["q"]), "attn/w_k": (d, w["kv"]), "attn/w_v": (d, w["kv"]),
                "attn/w_o": (w["q"], d)}
    f, fs = m["moe_intermediate_size"], m["moe_shared_expert_intermediate_size"]
    out = {"norm": (d,), "router": (d, m["n_routed_experts"]), "router_bias": (m["n_routed_experts"],),
           "shared/w_up": (d, fs), "shared/w_down": (fs, d)}
    for e in held_ids(m):
        out |= {f"expert{e}/w_up": (d, f), f"expert{e}/w_down": (f, d)}
    return out


def outer_leaves(m: dict) -> dict[str, tuple[int, ...]]:
    d = m["hidden_size"]
    return {"embed/patch": (m["patch"] ** 2 * 3, d), "embed/token": (m["vocab_size"], d),
            "final_norm": (d,), "head": (d, m["vocab_size"])}


def all_leaves(m: dict) -> dict[str, tuple[int, ...]]:
    out = dict(outer_leaves(m))
    for l, kind in enumerate(m["hybrid_override_pattern"]):
        out |= {f"layer{l}/{k}": v for k, v in layer_leaves(m, kind).items()}
    return out


def std(name: str, shape: tuple[int, ...], m: dict) -> float:
    """A matrix's deviation: ``leaf_gain[kind] / sqrt(fan in)``, the kind the
    leaf's last name (an expert's ``w_down`` is ``expert_w_down``, the shared
    one's ``shared_w_down``); a token's embedding has no fan in."""
    owner, _, kind = name.rpartition("/")
    if kind == "w_down":
        kind = "shared_w_down" if owner.endswith("shared") else "expert_w_down"
    gain = m.get("leaf_gain", {}).get(kind, 1.0)
    return gain if kind == "token" else gain / float(np.sqrt(shape[0]))


def blocks(shape: tuple[int, ...]) -> list[tuple[int, int]]:
    """The row ranges a leaf is drawn in: one for a leaf of up to
    ``BLOCK_VALUES`` values, else whole rows of at most that many a block."""
    if len(shape) < 2 or int(np.prod(shape)) <= BLOCK_VALUES:
        return [(0, shape[0])]
    rows = max(1, BLOCK_VALUES // int(np.prod(shape[1:])))
    return [(r, min(r + rows, shape[0])) for r in range(0, shape[0], rows)]


def make_block(seed: int, name: str, shape: tuple[int, ...], m: dict, block: int) -> np.ndarray:
    """Rows ``blocks(shape)[block]`` of leaf ``name``, float32."""
    from benchmark.reference import leaves

    ranges = blocks(shape)
    if len(ranges) == 1:
        return make_leaf(seed, name, shape, m)
    r0, r1 = ranges[block]
    return leaves.normal(seed, f"{name}#{block}", (r1 - r0, *shape[1:]), std(name, shape, m))


def make_leaf(seed: int, name: str, shape: tuple[int, ...], m: dict) -> np.ndarray:
    """Leaf ``name`` in float32. A gain (``*norm``) and ``d`` are 1 + 0.1 z;
    ``dt_bias`` is the inverse softplus of a step drawn log-uniform in
    ``time_step_min``-``time_step_max`` and floored at ``time_step_floor``;
    ``a_log`` the logarithm of a decay drawn uniform in 1-16; ``conv_b``
    0.1 z; the selection bias zero; every other leaf ``std * z``, z normal."""
    from benchmark.reference import leaves

    kind = name.rsplit("/", 1)[-1]
    if kind in ("norm", "final_norm", "d"):
        return 1.0 + leaves.normal(seed, name, shape, 0.1)
    if kind == "dt_bias":
        lo, hi = np.log(m.get("time_step_min", 1e-3)), np.log(m.get("time_step_max", 1e-1))
        dt = np.maximum(np.exp(leaves.generator(seed, name).uniform(lo, hi, shape)), m.get("time_step_floor", 1e-4))
        return (dt + np.log(-np.expm1(-dt))).astype(np.float32)
    if kind == "a_log":
        return np.log(leaves.generator(seed, name).uniform(1.0, 16.0, shape)).astype(np.float32)
    if kind == "conv_b":
        return leaves.normal(seed, name, shape, 0.1)
    if kind == "router_bias":
        return np.zeros(shape, np.float32)
    if len(blocks(shape)) > 1:
        return np.concatenate([make_block(seed, name, shape, m, b) for b in range(len(blocks(shape)))])
    return leaves.normal(seed, name, shape, std(name, shape, m))


# ------------------------------------------------------------------ the walk

def _low(w, control, axis=0):
    """A matrix as the control holds it, back in float32: int8 keeps 255
    levels a column; fp8 (e4m3) rounds every value."""
    if control == "int8":
        scale = jnp.abs(w).max(axis, keepdims=True) / 127.0
        return jnp.rint(w / scale) * scale
    if control == "fp8":
        return w.astype(jnp.float8_e4m3fn).astype(jnp.float32)
    return w


def _dense(x, w, control=None):
    """A layer's matrix product; under ``fp8`` both operands are rounded."""
    return jnp.matmul(_low(x, "fp8") if control == "fp8" else x, _low(w, control), precision=HI)


def _norm(x, gain, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * gain


def mixer(m: dict, w: dict, n, control=None, n_image: int | None = None):
    """The Mamba-2 mixer on ``n [T, D]``, token by token. ``n_image``: how
    many of the tokens are the image's (the rest the answer's), for the two
    controls that break the hand-over between them."""
    heads, dh, groups, state = m["mamba_num_heads"], m["mamba_head_dim"], m["n_groups"], m["ssm_state_size"]
    wd, taps = widths(m), m["conv_kernel"]
    proj = _dense(n, w["mixer/w_in"], control)
    z, xbc, dt = proj[:, :wd["inner"]], proj[:, wd["inner"]:wd["inner"] + wd["conv"]], proj[:, wd["inner"] + wd["conv"]:]

    def conv(inputs):
        padded = jnp.concatenate([jnp.zeros((taps - 1, inputs.shape[1]), inputs.dtype), inputs])
        return w["mixer/conv_b"] + sum(w["mixer/conv_w"][k] * padded[k:k + inputs.shape[0]] for k in range(taps))

    conved = conv(xbc)
    if control == "no_conv_tail" and n_image is not None:    # the answer's tokens see no input of the image's
        conved = jnp.concatenate([conved[:n_image], conv(xbc.at[:n_image].set(0.0))[n_image:]])
    xbc = jax.nn.silu(conved)
    u = xbc[:, :wd["inner"]].reshape(-1, heads, dh)
    b = jnp.repeat(xbc[:, wd["inner"]:wd["inner"] + wd["bc"]].reshape(-1, groups, state), heads // groups, axis=1)
    c = jnp.repeat(xbc[:, wd["inner"] + wd["bc"]:].reshape(-1, groups, state), heads // groups, axis=1)
    delta = jax.nn.softplus(dt + w["mixer/dt_bias"])                      # [T, heads]
    a = -jnp.exp(w["mixer/a_log"])
    keep = jnp.ones((n.shape[0],), jnp.float32)
    if control == "no_state_carry" and n_image is not None:  # the first answer token starts from a zero state
        keep = keep.at[n_image].set(0.0) if n_image < n.shape[0] else keep

    def token(h, inputs):
        u_t, b_t, c_t, d_t, keep_t = inputs
        h = jnp.exp(d_t * a)[:, None, None] * (keep_t * h) + (d_t[:, None] * u_t)[:, :, None] * b_t[:, None, :]
        return h, jnp.einsum("hpn,hn->hp", h, c_t, precision=HI)

    _, y = jax.lax.scan(token, jnp.zeros((heads, dh, state), jnp.float32), (u, b, c, delta, keep))
    y = y + w["mixer/d"][:, None] * u
    gated = y.reshape(-1, wd["inner"]) * jax.nn.silu(z)
    per_group = wd["inner"] // groups
    normed = _norm(gated.reshape(-1, groups, per_group), w["mixer/norm"].reshape(groups, per_group),
                   m["layer_norm_epsilon"])
    return _dense(normed.reshape(-1, wd["inner"]), w["mixer/w_out"], control)


def attention(m: dict, w: dict, n, control=None):
    t, hq, hk, dh = n.shape[0], m["num_attention_heads"], m["num_key_value_heads"], m["head_dim"]
    q = _dense(n, w["attn/w_q"], control).reshape(t, hq, dh)
    k = _dense(n, w["attn/w_k"], control).reshape(t, hk, dh)
    v = _dense(n, w["attn/w_v"], control).reshape(t, hk, dh)
    causal = jnp.tril(jnp.ones((t, t), bool))
    per = hq // hk
    out = []
    for g in range(hk):              # a key/value head and its query heads at a time: the scores are T x T a head
        s = jnp.einsum("qhd,kd->hqk", q[:, g * per:(g + 1) * per], k[:, g], precision=HI) / np.sqrt(dh)
        p = jax.nn.softmax(jnp.where(causal[None], s, -jnp.inf), axis=-1)
        out.append(jnp.einsum("hqk,kd->qhd", p, v[:, g], precision=HI))
    return _dense(jnp.concatenate(out, axis=1).reshape(t, hq * dh), w["attn/w_o"], control)


def _relu2(x, w_up, w_down, control):
    return _dense(jnp.square(jax.nn.relu(_dense(x, w_up, control))), w_down, control)


def route(m: dict, w: dict, n, control=None):
    """(weights [T, k], ids [T, k]): the bias moves the choice and not the weight."""
    s = jax.nn.sigmoid(jnp.matmul(n, w["router"], precision=HI))
    _, ids = jax.lax.top_k(s + w["router_bias"], m["num_experts_per_tok"])
    picked = jnp.take_along_axis(s, ids, axis=-1)
    if m.get("norm_topk_prob", True) and control != "no_topk_norm":
        picked = picked / jnp.sum(picked, axis=-1, keepdims=True)
    return m["routed_scaling_factor"] * picked, ids


def stack_experts(m: dict, w: dict) -> dict:
    """A layer's leaves with the held experts' matrices stacked in id order
    (``experts/w_up`` [E_held, D, F], ``experts/w_down`` [E_held, F, D]): what
    :func:`experts` walks. Numpy in, numpy out; other leaves as they are."""
    out = {k: v for k, v in w.items() if not k.startswith("expert")}
    for part in ("w_up", "w_down"):
        out[f"experts/{part}"] = np.stack([w[f"expert{e}/{part}"] for e in held_ids(m)])
    return out


def experts(m: dict, w: dict, n, control=None):
    """Each token's picks, one by one: a held expert's part, nothing for an
    absent one; the shared expert for every token. (Written as a loop over
    the held experts with each token's weight on it, zero where it did not
    pick it: the same sum. ``w``: :func:`stack_experts`'s.)"""
    weight, ids = route(m, w, n, control)
    out = jnp.zeros_like(n)
    if control != "no_held_experts":
        def one(acc, held):
            e, w_up, w_down = held
            w_e = jnp.sum(jnp.where(ids == e, weight, 0.0), axis=1)
            return acc + w_e[:, None] * _relu2(n, w_up, w_down, control), None

        out, _ = jax.lax.scan(one, out, (jnp.asarray(list(held_ids(m)), jnp.int32), w["experts/w_up"], w["experts/w_down"]))
    if control != "no_shared_expert":
        out += _relu2(n, w["shared/w_up"], w["shared/w_down"], control)
    return out


def layer(m: dict, kind: str, w: dict, x, control: str | None = None, n_image: int | None = None):
    """One layer of ``kind`` on ``x [T, D]``; ``w``: the layer's leaves."""
    n = _norm(x, w["norm"], m["layer_norm_epsilon"])
    if kind == "M":
        return x + mixer(m, w, n, control, n_image)
    if kind == "*":
        return x + attention(m, w, n, control)
    return x + experts(m, w, n, control)


def embed(w_patch, tokens: np.ndarray, id_rows) -> jnp.ndarray:
    """The patch tokens through the stand-in embedding, then the answer ids'
    rows of the token embedding (``id_rows [n, D]``, already looked up)."""
    x = jnp.matmul(jnp.asarray(tokens), w_patch, precision=HI)
    return jnp.concatenate([x, jnp.asarray(id_rows)]) if len(id_rows) else x


def head_probs(m: dict, w: dict, rows) -> jnp.ndarray:
    """The distributions after the given rows of the last layer's output."""
    logits = jnp.matmul(_norm(rows, w["final_norm"], m["layer_norm_epsilon"]), w["head"], precision=HI)
    return jax.nn.softmax(logits, axis=-1)


def forward(m: dict, w: dict, tokens: np.ndarray, ids, steps: int, control: str | None = None) -> np.ndarray:
    """The whole model on one image (``w``: every leaf, by its full name):
    the distributions of the last ``steps`` positions, [steps, vocab]. For
    the tests and small sizes; the check child walks layer by layer."""
    x = embed(w["embed/patch"], tokens, w["embed/token"][jnp.asarray(ids, jnp.int32)] if len(ids) else ())
    for l, kind in enumerate(m["hybrid_override_pattern"]):
        pre = f"layer{l}/"
        w_l = {k[len(pre):]: v for k, v in w.items() if k.startswith(pre)}
        if kind == "E":
            w_l = stack_experts(m, {k: np.asarray(v) for k, v in w_l.items()})
        x = layer(m, kind, w_l, x, control, len(tokens))
    return np.asarray(head_probs(m, w, x[-steps:]))


def answer(m: dict, w: dict, tokens: np.ndarray, control: str | None = None) -> list[list]:
    """What a server of ``w`` answers for one image, greedily: ``answer_steps``
    top-k lists of [id, score], each step a forward of its own."""
    out = []
    for _ in range(m["answer_steps"]):
        dist = forward(m, w, tokens, [step[0][0] for step in out], 1, control)[0]
        out.append([[int(c), float(dist[c])] for c in np.argsort(-dist)[:m["topk"]]])
    return out
