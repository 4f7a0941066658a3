"""LongCat-Flash's language model behind the image path: the first
``task: "generate"`` family of this zoo (models/decoder.py has the contract).

One published layer is a *double layer* on ``x [T, D]``::

    h1 = x  + MLA_0(N(x))
    u  = N(h1);  m = MoE(u);  h2 = h1 + FFN_0(u)      # the expert layer starts here ...
    h3 = h2 + MLA_1(N(h2))
    y  = h3 + FFN_1(N(h3)) + m                         # ... and lands here (the shortcut)

with RMSNorm ``N``, SwiGLU ``FFN``, latent attention ``MLA`` (ops/mla.py)
and a routed expert layer with zero (identity) experts that is told which
experts it holds (ops/experts.py). The vision tower is not modelled: one
linear patch embedding stands in for it (ops/image.py::patch_tokens).

:func:`answer` is what ``jit_serve`` runs after the patches: prefill of all
layers (filling the latent cache: 576 values a token an
attention), final norm, head, top-k: step 1; then ``answer_steps - 1``
more steps, each embedding the id the last step put first and running the
absorbed attention against the cache. It returns the steps' top-k lists
and a vector of counters (``COUNTERS``).

Weights are functional: a flat dict, one array a matrix and one stack of
the held experts a layer (:func:`param_shapes`). :func:`leaf_table` names
each leaf of a ``--ckpt`` export and where it lands (models/adapter.py
reads them one at a time).
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from ..ops import experts as experts_op
from ..ops import mla
from . import decoder as shared
from .decoder import layer_params as _layer, mm as _mm, out as _out, rmsnorm

# What `answer` counts, a call: /stats -> batcher.lifecycle.<name>_total sums them over batches.
COUNTERS = ("images", "tokens_real", "token_slots", "token_slots_pad", "picks", "zero_picks", "held_picks",
            "held_expert_load_max", "held_expert_load_mean", "decode_steps")


@dataclasses.dataclass(frozen=True)
class Config:
    hidden_size: int = 6144
    ffn_hidden_size: int = 12288
    expert_ffn_hidden_size: int = 2048
    num_layers: int = 28
    num_attention_heads: int = 64
    kv_lora_rank: int = 512
    q_lora_rank: int = 1536
    qk_rope_head_dim: int = 64
    qk_nope_head_dim: int = 128
    v_head_dim: int = 128
    n_routed_experts: int = 512        # the router's routed outputs, as published
    zero_expert_num: int = 256
    moe_topk: int = 12
    routed_scaling_factor: float = 6.0
    experts_held: int = 512            # how many routed experts live here ...
    experts_held_first: int = 0        # ... from this id on
    vocab_size: int = 131072           # rows of embedding and head held here
    rms_norm_eps: float = 1e-5
    rope_theta: float = 1e7
    patch: int = 32
    answer_steps: int = 4
    max_token_slots: int = 16384       # the most token slots (rows x a canvas's tokens) one call may hold

    @classmethod
    def from_dict(cls, d: dict) -> "Config":
        return shared.config_from(cls, d)

    @property
    def mla_scale_q(self) -> float:      # mla_scale_q_lora: sqrt(hidden / q_lora_rank)
        return float(np.sqrt(self.hidden_size / self.q_lora_rank))

    @property
    def mla_scale_kv(self) -> float:     # mla_scale_kv_lora: sqrt(hidden / kv_lora_rank)
        return float(np.sqrt(self.hidden_size / self.kv_lora_rank))

    def token_slots(self, canvas_s: int) -> int:
        return (canvas_s // self.patch) ** 2


NORMS = ("attn0", "ffn0", "attn1", "ffn1")


def layer_shapes(c: Config) -> dict[str, tuple[int, ...]]:
    """One layer's parameters, named without their ``layer<l>/`` prefix. A
    matrix is an array of its own (a slice of a stack would be copied out
    before every product that reads it: 2.5 GB a layer at the published
    widths); only the held experts are stacked, and the grouped product
    picks an expert's block by index."""
    d, h = c.hidden_size, c.num_attention_heads
    dq, dk, dv = c.qk_nope_head_dim + c.qk_rope_head_dim, c.qk_nope_head_dim, c.v_head_dim
    out = {f"norm/{n}": (d,) for n in NORMS}
    for a in (0, 1):
        out |= {f"mla{a}/w_dq": (d, c.q_lora_rank), f"mla{a}/q_norm": (c.q_lora_rank,),
                f"mla{a}/w_uq": (c.q_lora_rank, h * dq),
                f"mla{a}/w_dkv": (d, c.kv_lora_rank + c.qk_rope_head_dim), f"mla{a}/kv_norm": (c.kv_lora_rank,),
                f"mla{a}/w_ukv": (c.kv_lora_rank, h * (dk + dv)), f"mla{a}/w_o": (h * dv, d),
                f"ffn{a}/w_gate": (d, c.ffn_hidden_size), f"ffn{a}/w_up": (d, c.ffn_hidden_size),
                f"ffn{a}/w_down": (c.ffn_hidden_size, d)}
    out["router"] = (d, c.n_routed_experts + c.zero_expert_num)
    out |= {"experts/w_gate": (c.experts_held, d, c.expert_ffn_hidden_size),
            "experts/w_up": (c.experts_held, d, c.expert_ffn_hidden_size),
            "experts/w_down": (c.experts_held, c.expert_ffn_hidden_size, d)}
    return out


def param_shapes(c: Config) -> dict[str, tuple[int, ...]]:
    """The flat parameter dict's keys and shapes."""
    d = c.hidden_size
    out = {"embed/patch": (c.patch * c.patch * 3, d), "embed/token": (c.vocab_size, d),
           "final_norm": (d,), "head": (d, c.vocab_size)}
    for l in range(c.num_layers):
        out |= {f"layer{l}/{k}": v for k, v in layer_shapes(c).items()}
    return out


def leaf_table(c: Config) -> list[tuple[str, tuple[int, ...], str, tuple[int, ...]]]:
    """(leaf name in an export, its shape, the parameter it belongs to, its
    index there): an export names every expert's matrices on their own
    (``layer0/expert3/w_up``), the unit it is written and read in; every
    other leaf is a parameter as it stands."""
    out = []
    for name, shape in param_shapes(c).items():
        layer, _, rest = name.partition("/")
        if rest.startswith("experts/"):
            out += [(f"{layer}/expert{c.experts_held_first + e}/{rest[len('experts/'):]}", shape[1:], name, (e,))
                    for e in range(shape[0])]
        else:
            out.append((name, shape, name, ()))
    return out


def init_params(c: Config, seed: int = 0) -> dict[str, np.ndarray]:
    """Seeded float32 weights for a server booted without ``--ckpt``
    (tests, smoke): unit-variance activations, gains near one."""
    rs = np.random.Generator(np.random.PCG64(seed))
    out = {}
    for name, shape in param_shapes(c).items():
        if name.endswith("norm") or "/norm/" in name:
            out[name] = (1.0 + 0.1 * rs.standard_normal(shape)).astype(np.float32)
        else:
            fan_in = 1.0 if name == "embed/token" else shape[-2]
            out[name] = (rs.standard_normal(shape) / np.sqrt(fan_in)).astype(np.float32)
    return out


# ------------------------------------------------------------------ the blocks

def _ffn(x, p, a: int):
    return shared.dense_ffn(x, p[f"ffn{a}/w_gate"], p[f"ffn{a}/w_up"], p[f"ffn{a}/w_down"])


def _mla_latents(c: Config, p, a: int, xn, positions):
    """The query in its two parts and the latents a token leaves in the
    cache: q_n [.., H, dn], q_r [.., H, dr] (rotated), c_kv [.., C], k_r
    [.., dr] (rotated)."""
    h, dn, dr = c.num_attention_heads, c.qk_nope_head_dim, c.qk_rope_head_dim
    c_q = rmsnorm(_mm(xn, p[f"mla{a}/w_dq"]), p[f"mla{a}/q_norm"], c.rms_norm_eps)
    q = _mm((c_q.astype(jnp.float32) * c.mla_scale_q).astype(xn.dtype), p[f"mla{a}/w_uq"])
    q = q.reshape(*q.shape[:-1], h, dn + dr)
    ckv = _mm(xn, p[f"mla{a}/w_dkv"])
    c_kv = rmsnorm(ckv[..., : c.kv_lora_rank], p[f"mla{a}/kv_norm"], c.rms_norm_eps)
    c_kv = (c_kv.astype(jnp.float32) * c.mla_scale_kv).astype(xn.dtype)
    k_r = mla.rope(ckv[..., c.kv_lora_rank:], positions, c.rope_theta)
    return q[..., :dn], mla.rope(q[..., dn:], positions, c.rope_theta), c_kv, k_r


def _mla_prefill(c: Config, p, a: int, xn, lengths, positions):
    b, t, _ = xn.shape
    h, dn, dv = c.num_attention_heads, c.qk_nope_head_dim, c.v_head_dim
    q_n, q_r, c_kv, k_r = _mla_latents(c, p, a, xn, positions)
    kv = _mm(c_kv, p[f"mla{a}/w_ukv"]).reshape(b, t, h, dn + dv)
    heads_first = lambda z: z.transpose(0, 2, 1, 3)
    o = mla.core(heads_first(q_n), heads_first(q_r), heads_first(kv[..., :dn]), k_r, heads_first(kv[..., dn:]),
                 lengths, 1.0 / float(np.sqrt(dn + c.qk_rope_head_dim)))
    return _out(o.transpose(0, 2, 1, 3).reshape(b, t, h * dv), p[f"mla{a}/w_o"]), c_kv, k_r


def _mla_decode(c: Config, p, a: int, xn, lengths, positions, cache, tail, step: int):
    """One token a row: its latents go into ``tail`` at ``step``, then the
    absorbed attention over the image's cache and the tail so far."""
    h, dn, dv = c.num_attention_heads, c.qk_nope_head_dim, c.v_head_dim
    q_n, q_r, c_kv, k_r = _mla_latents(c, p, a, xn[:, None, :], positions[:, None])
    tail_c = jax.lax.dynamic_update_slice_in_dim(tail[0], c_kv, step, 1)
    tail_r = jax.lax.dynamic_update_slice_in_dim(tail[1], k_r, step, 1)
    w_ukv = p[f"mla{a}/w_ukv"].reshape(c.kv_lora_rank, h, dn + dv)
    o = mla.absorbed_step(q_n[:, 0], q_r[:, 0], w_ukv[..., :dn], w_ukv[..., dn:], cache[0], cache[1],
                          tail_c, tail_r, lengths, step + 1, 1.0 / float(np.sqrt(dn + c.qk_rope_head_dim)))
    return _out(o.reshape(-1, h * dv).astype(xn.dtype), p[f"mla{a}/w_o"]), (tail_c, tail_r)


def _moe(c: Config, p, u, valid):
    flat = u.reshape(-1, u.shape[-1])
    m, counters = experts_op.expert_layer(
        flat, valid.reshape(-1), p["router"], p["experts/w_gate"], p["experts/w_up"], p["experts/w_down"],
        topk=c.moe_topk, scale=c.routed_scaling_factor, n_routed=c.n_routed_experts, held_first=c.experts_held_first)
    return m.reshape(u.shape), counters


def _double_layer(c: Config, p, x, valid, attend):
    """The equations at the top, on ``x`` of any leading shape; ``attend(a,
    xn)`` is attention ``a`` of this layer and returns (output, what it
    leaves behind). The residual stream ``x`` is float32; what a matrix
    product reads (a normed activation, a weight) is the served dtype, and
    every product accumulates in float32."""
    dtype = p["router"].dtype
    norm = lambda i, z: rmsnorm(z, p[f"norm/{NORMS[i]}"], c.rms_norm_eps, dtype)
    with jax.named_scope("mla"):
        o, left0 = attend(0, norm(0, x))
    h1 = x + o
    u = norm(1, h1)
    m, counters = _moe(c, p, u, valid)
    with jax.named_scope("dense_ffn"):
        h2 = h1 + _ffn(u, p, 0)
    with jax.named_scope("mla"):
        o, left1 = attend(1, norm(2, h2))
    h3 = h2 + o
    with jax.named_scope("dense_ffn"):
        y = h3 + _ffn(norm(3, h3), p, 1) + m
    return y, (left0, left1), counters


def _top(c: Config, params, hidden, topk: int):
    return shared.top(hidden, params["final_norm"], params["head"], c.rms_norm_eps, topk)


def answer(c: Config, params: dict, tokens, lengths, topk: int):
    """``tokens`` [B, T, patch*patch*3] (normalised pixels, padding slots
    zero), ``lengths`` [B] real tokens a row -> (scores [B, steps, k]
    float32, ids [B, steps, k] int32, counters [len(COUNTERS)] float32)."""
    b, t, _ = tokens.shape
    dtype = params["embed/patch"].dtype
    layers = [_layer(params, l) for l in range(c.num_layers)]
    valid = jnp.arange(t)[None, :] < lengths[:, None]
    positions = jnp.broadcast_to(jnp.arange(t)[None, :], (b, t))
    with jax.named_scope("patches"):
        x = _out(tokens.astype(dtype), params["embed/patch"])

    total = dict.fromkeys(COUNTERS, jnp.float32(0))
    cache = []          # a layer: ((c_kv, k_r) of attention 0, the same of attention 1)
    for p in layers:
        def attend(a, xn, p=p):
            with jax.named_scope("mla_prefill"):
                o, c_kv, k_r = _mla_prefill(c, p, a, xn, lengths, positions)
            return o, (c_kv, k_r)

        x, left, counters = _double_layer(c, p, x, valid, attend)
        cache.append(left)
        total |= {k: total[k] + v for k, v in counters.items()}
    last = jnp.take_along_axis(x, jnp.maximum(lengths - 1, 0)[:, None, None], axis=1)[:, 0]
    first = _top(c, params, last, topk)

    n_tail = c.answer_steps - 1
    real_row = lengths > 0
    out_scores, out_ids = first[0][:, None], first[1][:, None]
    if n_tail:
        tail0 = [tuple((jnp.zeros((b, n_tail, c.kv_lora_rank), dtype), jnp.zeros((b, n_tail, c.qk_rope_head_dim), dtype))
                       for _ in (0, 1)) for _ in layers]

        def step(carry, s):
            """One more answer step for every row: embed the id the last
            step put first, every layer against its cache, the head."""
            ids, tails, picks = carry
            x1 = params["embed/token"][ids].astype(jnp.float32)
            new_tails = []
            for p, layer_cache, layer_tail in zip(layers, cache, tails):
                left = {}

                def attend(a, xn, p=p, layer_cache=layer_cache, layer_tail=layer_tail, left=left):
                    with jax.named_scope("mla_decode"):
                        o, left[a] = _mla_decode(c, p, a, xn, lengths, lengths + s, layer_cache[a], layer_tail[a], s)
                    return o, None

                x1, _, counters = _double_layer(c, p, x1, real_row, attend)
                new_tails.append((left[0], left[1]))
                picks = {k: picks[k] + counters[k] for k in picks}
            scores, top_ids = _top(c, params, x1, topk)
            return (top_ids[:, 0], new_tails, picks), (scores, top_ids)

        picks0 = {k: total[k] for k in ("picks", "zero_picks", "held_picks")}
        (_, _, picks), (more_scores, more_ids) = jax.lax.scan(
            step, (first[1][:, 0], tail0, picks0), jnp.arange(n_tail, dtype=jnp.int32))
        total |= picks
        out_scores = jnp.concatenate([out_scores, more_scores.transpose(1, 0, 2)], axis=1)
        out_ids = jnp.concatenate([out_ids, more_ids.transpose(1, 0, 2)], axis=1)

    total["tokens_real"] = lengths.sum().astype(jnp.float32)
    total["images"] = real_row.sum().astype(jnp.float32)
    total["token_slots"] = jnp.float32(b * t)
    total["token_slots_pad"] = total["token_slots"] - total["tokens_real"]
    total["decode_steps"] = real_row.sum().astype(jnp.float32) * n_tail
    return out_scores, out_ids, jnp.stack([total[k] for k in COUNTERS])
