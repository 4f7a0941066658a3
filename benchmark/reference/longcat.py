"""The plain reference of LongCat-Flash's language model behind a patch
embedding: ``jax.numpy``, float32, ``highest``; no cache, no kernel, no
batching, full T x T attention an image. It imports nothing of the program.

One published layer (a "double layer") on ``x [T, D]``, RMSNorm ``N`` with
a learned gain and eps 1e-5::

    h1 = x  + MLA_0(N(x))
    u  = N(h1);  m = MoE(u);  h2 = h1 + FFN_0(u)
    h3 = h2 + MLA_1(N(h2))
    y  = h3 + FFN_1(N(h3)) + m                      # the shortcut: m lands a block later

``FFN``: SwiGLU. ``MLA``: ``c_q = s_q N(x W_dq)``, ``q = c_q W_uq`` (per
head 128 without position | 64 rotary); ``[c_kv | k_r] = x W_dkv``, ``c_kv
= s_kv N(c_kv)``, per head ``[k_n | v] = c_kv W_ukv``; ``k_r`` one rotary
key for all heads; scores ``(q_n.k_n + q_r.k_r) / sqrt(192)``, causal
softmax, ``W_o``. ``MoE``: ``p = softmax(u W_r)`` over routed + zero
experts, the ``moe_topk`` largest kept, weight ``routed_scaling_factor *
p_e`` (not renormalised); a routed expert is a SwiGLU, a zero expert the
identity. Only the experts held here (``experts_held`` from id 0) and the
zero experts add: the absent ones are other chips' part, in the program and
here alike.

Departures from the published description, each also under ``assumed`` in
the configuration's file:

- the vision tower is one linear patch embedding (every whole 32 x 32 block
  of an image's own pixels, ``/127.5 - 1``, raster order); audio and the
  codec decoder are absent;
- the router's selection bias (``e_score_correction_bias``) is zero;
- ``mla_scale_q_lora`` and ``mla_scale_kv_lora`` multiply the normed
  latents: ``s_q = sqrt(D / q_lora_rank)``, ``s_kv = sqrt(D / kv_lora_rank)``;
- rotary pairs are the two halves (i, i + 32), theta 1e7, no YaRN;
  positions are the raster index, the answer's tokens follow on;
- weights are normal draws from ``(seed, leaf name)`` (``leaves.py``) with
  the deviation :func:`std` gives a leaf.

An answer has ``answer_steps`` distributions: after the image, then after
each id that was put first. Attention is causal, so one forward over the
image's tokens and those ids yields every step at once: position ``T - 1 +
s`` is exactly what a forward over the first ``T + s`` tokens ends in.

``control`` names what must read not correct (:data:`CONTROLS`): a lower
precision of the dense and expert weights, or a part of the layer left out.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

HI = jax.lax.Precision.HIGHEST
CONTROLS = ("int8", "fp8", "no_held_experts", "no_zero_experts", "no_shortcut")
NORMS = ("attn0", "ffn0", "attn1", "ffn1")
HEAD_BLOCK = 16   # heads of one block of T x T scores: 16 x 3075**2 x 4 bytes is 0.6 GB


# ------------------------------------------------------------------ leaves

def layer_leaves(m: dict) -> dict[str, tuple[int, ...]]:
    """One layer's leaves, named without their ``layer<l>/`` prefix."""
    d, h = m["hidden_size"], m["num_attention_heads"]
    dn, dr, dv = m["qk_nope_head_dim"], m["qk_rope_head_dim"], m["v_head_dim"]
    out = {f"norm/{n}": (d,) for n in NORMS}
    for a in (0, 1):
        out |= {f"mla{a}/w_dq": (d, m["q_lora_rank"]), f"mla{a}/q_norm": (m["q_lora_rank"],),
                f"mla{a}/w_uq": (m["q_lora_rank"], h * (dn + dr)),
                f"mla{a}/w_dkv": (d, m["kv_lora_rank"] + dr), f"mla{a}/kv_norm": (m["kv_lora_rank"],),
                f"mla{a}/w_ukv": (m["kv_lora_rank"], h * (dn + dv)), f"mla{a}/w_o": (h * dv, d),
                f"ffn{a}/w_gate": (d, m["ffn_hidden_size"]), f"ffn{a}/w_up": (d, m["ffn_hidden_size"]),
                f"ffn{a}/w_down": (m["ffn_hidden_size"], d)}
    out["router"] = (d, m["n_routed_experts"] + m["zero_expert_num"])
    for e in range(m["experts_held"]):
        out |= {f"expert{e}/w_gate": (d, m["expert_ffn_hidden_size"]), f"expert{e}/w_up": (d, m["expert_ffn_hidden_size"]),
                f"expert{e}/w_down": (m["expert_ffn_hidden_size"], d)}
    return out


def outer_leaves(m: dict) -> dict[str, tuple[int, ...]]:
    d = m["hidden_size"]
    return {"embed/patch": (m["patch"] ** 2 * 3, d), "embed/token": (m["vocab_size"], d),
            "final_norm": (d,), "head": (d, m["vocab_size"])}


def all_leaves(m: dict) -> dict[str, tuple[int, ...]]:
    out = dict(outer_leaves(m))
    for l in range(m["num_layers"]):
        out |= {f"layer{l}/{k}": v for k, v in layer_leaves(m).items()}
    return out


def std(name: str, shape: tuple[int, ...], m: dict) -> float:
    """A leaf's deviation: ``leaf_gain[kind] / sqrt(fan in)``, the kind
    being the leaf's last name (an expert's ``w_down`` is ``expert_w_down``);
    a token's embedding has no fan in. ``model.leaf_gain`` has the table and
    the configuration's ``assumed`` the reason for each entry."""
    kind = name.rsplit("/", 1)[-1]
    if "/expert" in f"/{name}" and kind == "w_down":
        kind = "expert_w_down"
    gain = m.get("leaf_gain", {}).get(kind, 1.0)
    return gain if kind == "token" else gain / float(np.sqrt(shape[0]))


def make_leaf(seed: int, name: str, shape: tuple[int, ...], m: dict) -> np.ndarray:
    """Leaf ``name`` in float32: a gain (``*norm*``) is 1 + 0.1 z, every
    other leaf ``std * z``, z normal from ``(seed, name)``."""
    from benchmark.reference import leaves

    if name.endswith("norm") or "/norm/" in name:
        return 1.0 + leaves.normal(seed, name, shape, 0.1)
    return leaves.normal(seed, name, shape, std(name, shape, m))


# ------------------------------------------------------------------ the walk

def patches(pixels: np.ndarray, patch: int) -> np.ndarray:
    """[h, w, 3] uint8 -> [tokens, patch * patch * 3] in [-1, 1]: every whole block, row by row."""
    h, w = pixels.shape[0] // patch, pixels.shape[1] // patch
    x = pixels[:h * patch, :w * patch].reshape(h, patch, w, patch, 3).transpose(0, 2, 1, 3, 4)
    return x.reshape(h * w, -1).astype(np.float32) / 127.5 - 1.0


def _mm(a, b):
    return jnp.matmul(a, b, precision=HI)


def _norm(x, gain, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * gain


def _rope(x, theta):
    """x [T, (H,) d]: position t rotates the pairs (i, i + d/2)."""
    d = x.shape[-1]
    ang = jnp.arange(x.shape[0], dtype=jnp.float32)[:, None] * (1.0 / theta ** (jnp.arange(0, d, 2) / d))[None, :]
    if x.ndim == 3:
        ang = ang[:, None, :]
    x1, x2 = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * jnp.cos(ang) - x2 * jnp.sin(ang), x2 * jnp.cos(ang) + x1 * jnp.sin(ang)], -1)


def _low(w, control, axis=0):
    """A matrix as the control holds it, back in float32: int8 keeps 255
    levels a column; fp8 (e4m3) rounds every value."""
    if control == "int8":
        scale = jnp.abs(w).max(axis, keepdims=True) / 127.0
        return jnp.rint(w / scale) * scale
    if control == "fp8":
        return w.astype(jnp.float8_e4m3fn).astype(jnp.float32)
    return w


def _swiglu(x, w_gate, w_up, w_down, control):
    act = (lambda z: _low(z, "fp8")) if control == "fp8" else (lambda z: z)   # fp8 computes: both operands rounded
    hidden = jax.nn.silu(_mm(act(x), _low(w_gate, control))) * _mm(act(x), _low(w_up, control))
    return _mm(act(hidden), _low(w_down, control))


def _mla(m, w, a, xn):
    t = xn.shape[0]
    h, dn, dr, dv = m["num_attention_heads"], m["qk_nope_head_dim"], m["qk_rope_head_dim"], m["v_head_dim"]
    eps, d = m["rms_norm_eps"], m["hidden_size"]
    c_q = np.sqrt(d / m["q_lora_rank"]) * _norm(_mm(xn, w[f"mla{a}/w_dq"]), w[f"mla{a}/q_norm"], eps)
    q = _mm(c_q, w[f"mla{a}/w_uq"]).reshape(t, h, dn + dr)
    ckv = _mm(xn, w[f"mla{a}/w_dkv"])
    c_kv = np.sqrt(d / m["kv_lora_rank"]) * _norm(ckv[:, :m["kv_lora_rank"]], w[f"mla{a}/kv_norm"], eps)
    kv = _mm(c_kv, w[f"mla{a}/w_ukv"]).reshape(t, h, dn + dv)
    q_n, q_r = q[..., :dn], _rope(q[..., dn:], m["rope_theta"])
    k_n, v, k_r = kv[..., :dn], kv[..., dn:], _rope(ckv[:, m["kv_lora_rank"]:], m["rope_theta"])
    causal = jnp.tril(jnp.ones((t, t), bool))
    out = []
    for h0 in range(0, h, HEAD_BLOCK):   # a block of heads at a time: the scores are T x T a head
        hs = slice(h0, h0 + HEAD_BLOCK)
        s = (jnp.einsum("qhd,khd->hqk", q_n[:, hs], k_n[:, hs], precision=HI)
             + jnp.einsum("qhd,kd->hqk", q_r[:, hs], k_r, precision=HI)) / np.sqrt(dn + dr)
        p = jax.nn.softmax(jnp.where(causal[None], s, -jnp.inf), axis=-1)
        out.append(jnp.einsum("hqk,khd->qhd", p, v[:, hs], precision=HI))
    return _mm(jnp.concatenate(out, axis=1).reshape(t, h * dv), w[f"mla{a}/w_o"])


def _moe(m, w, u, control):
    """Each token's picks, one by one: a held expert's SwiGLU, a zero
    expert's identity, nothing for an absent one. (Written as a loop over
    the held experts with each token's weight on it, zero where it did not
    pick it: the same sum.)"""
    n_routed, held = m["n_routed_experts"], m["experts_held"]
    p = jax.nn.softmax(_mm(u, w["router"]), axis=-1)
    top_p, ids = jax.lax.top_k(p, m["moe_topk"])
    weight = m["routed_scaling_factor"] * top_p                                   # not renormalised
    out = jnp.zeros_like(u)
    if control != "no_held_experts":
        for e in range(held):
            w_e = jnp.sum(jnp.where(ids == e, weight, 0.0), axis=1)
            out += w_e[:, None] * _swiglu(u, w[f"expert{e}/w_gate"], w[f"expert{e}/w_up"], w[f"expert{e}/w_down"], control)
    if control != "no_zero_experts":
        out += jnp.sum(jnp.where(ids >= n_routed, weight, 0.0), axis=1)[:, None] * u   # E(u) = u
    return out


def double_layer(m: dict, w: dict, x, control: str | None = None):
    """One published layer on ``x [T, D]``; ``w``: the layer's leaves."""
    eps = m["rms_norm_eps"]
    ffn = lambda a, z: _swiglu(z, w[f"ffn{a}/w_gate"], w[f"ffn{a}/w_up"], w[f"ffn{a}/w_down"], control)
    h1 = x + _mla(m, w, 0, _norm(x, w["norm/attn0"], eps))
    u = _norm(h1, w["norm/ffn0"], eps)
    moe = _moe(m, w, u, control)
    h2 = h1 + ffn(0, u)
    if control == "no_shortcut":
        h2, moe = h2 + moe, 0.0          # the expert layer's sum added where a plain layer would add it
    h3 = h2 + _mla(m, w, 1, _norm(h2, w["norm/attn1"], eps))
    return h3 + ffn(1, _norm(h3, w["norm/ffn1"], eps)) + moe


def embed(w: dict, tokens: np.ndarray, ids) -> jnp.ndarray:
    x = _mm(jnp.asarray(tokens), w["embed/patch"])
    return jnp.concatenate([x, w["embed/token"][jnp.asarray(ids, jnp.int32)]]) if len(ids) else x


def head_probs(m: dict, w: dict, rows) -> jnp.ndarray:
    """The distributions after the given rows of the last layer's output."""
    return jax.nn.softmax(_mm(_norm(rows, w["final_norm"], m["rms_norm_eps"]), w["head"]), axis=-1)


def forward(m: dict, w: dict, tokens: np.ndarray, ids, steps: int, control: str | None = None) -> np.ndarray:
    """The whole model on one image (``w``: every leaf, by its full name):
    the distributions of the last ``steps`` positions, [steps, vocab]. For
    the tests and small sizes; the check child walks layer by layer."""
    x = embed(w, tokens, ids)
    for l in range(m["num_layers"]):
        pre = f"layer{l}/"
        x = double_layer(m, {k[len(pre):]: v for k, v in w.items() if k.startswith(pre)}, x, control)
    return np.asarray(head_probs(m, w, x[-steps:]))


def answer(m: dict, w: dict, tokens: np.ndarray, control: str | None = None) -> list[list]:
    """What a server of ``w`` answers for one image, greedily: ``answer_steps``
    top-k lists of [id, score], each step a forward of its own."""
    out = []
    for _ in range(m["answer_steps"]):
        dist = forward(m, w, tokens, [step[0][0] for step in out], 1, control)[0]
        out.append([[int(c), float(dist[c])] for c in np.argsort(-dist)[:m["topk"]]])
    return out
