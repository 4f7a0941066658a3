"""Brumby-14B-Base's language model behind the image path: the third
``task: "generate"`` family of this zoo (models/decoder.py has the contract),
attention-free: every layer's mixer is gated power retention of degree 2
(Gelada, Buckman, Zhang, Bhaskar, "Scaling Context Requires Rethinking
Attention", arXiv:2507.04239; Manifest AI's Brumby-14B-Base release,
2025-10, retrained from Qwen3-14B).

A layer on ``x``, RMSNorm ``N`` (eps ``rms_norm_eps``)::

    n = N(x)
    q_h = rope(N_d(n W_q)_h),  k_j = rope(N_d(n W_k)_j),  v_j = (n W_v)_j    40 query over 8 key/value heads of 128
    log g_j = logsigmoid(n W_g[:, j] + b_g[j])                               a decay a key/value head and token
    y_h = retention(q_h, k_j, v_j, g_j),  j = h // 5                         ops/retention.py
    x <- x + concat(y) W_o;  x <- x + SwiGLU(N(x))

``N_d`` is Qwen3's per-head norm over 128; ``rope`` rotary positions on
half-pairs at ``rope_theta`` (ops/mla.py), the patch index in the prefill
and the row's length onwards in the steps. The vision tower is not
modelled: one linear patch embedding stands in for it
(ops/image.py::patch_tokens).

:func:`answer` is what ``jit_serve`` runs after the patches: prefill of all
layers, which leaves **one kind of state** a row and layer, a matrix over
the degree-2 features (``S`` [8, 128, D] and the normaliser ``z`` [8, 1,
D], float32: 35.9 MB at ``D`` 8,704), taken after the row's last real token
(a padding slot neither decays nor adds); then ``answer_steps - 1`` more
steps, one token a row through those states, updated in place, each
embedding the id the step before put first. The states live and die with
the call; there is no key/value cache. It returns the steps' top-k lists
and a vector of counters (``COUNTERS``).

Weights are a flat dict, one array a matrix (:func:`param_shapes`); every
leaf of a ``--ckpt`` export is a parameter as it stands (:func:`leaf_table`).
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from ..ops import mla, retention, ssd
from . import decoder as shared
from .decoder import out as _out, rmsnorm

# What `answer` counts, a call: /stats -> batcher.lifecycle.<name>_total sums them over batches. A chunk is a row's
# chunk_size slots in one layer (its key/value heads alike), skipped when wholly past the row's length; a step is
# cached when it went one token through the carried states (every step after an image's first).
COUNTERS = ("images", "tokens_real", "token_slots", "token_slots_pad", "retention_chunks",
            "retention_chunks_skipped", "answer_steps", "answer_steps_cached")


@dataclasses.dataclass(frozen=True)
class Config:
    hidden_size: int = 5120
    num_hidden_layers: int = 40
    num_attention_heads: int = 40
    num_key_value_heads: int = 8
    head_dim: int = 128
    intermediate_size: int = 17408
    vocab_size: int = 151936
    rms_norm_eps: float = 1e-6
    rope_theta: float = 1e6
    chunk_size: int = 128              # slots of one chunk of the retention's chunked form
    patch: int = 32
    answer_steps: int = 64
    max_token_slots: int = 16384       # the most token slots (rows x a canvas's tokens) one call may hold

    @classmethod
    def from_dict(cls, d: dict) -> "Config":
        return shared.config_from(cls, d)

    def token_slots(self, canvas_s: int) -> int:
        return (canvas_s // self.patch) ** 2

    @property
    def per(self) -> int:              # query heads a key/value head serves
        return self.num_attention_heads // self.num_key_value_heads


def layer_shapes(c: Config) -> dict[str, tuple[int, ...]]:
    """One layer's parameters, named without their ``layer<l>/`` prefix."""
    d, hq, hk, dh, f = c.hidden_size, c.num_attention_heads, c.num_key_value_heads, c.head_dim, c.intermediate_size
    return {"norm": (d,), "attn/w_q": (d, hq * dh), "attn/w_k": (d, hk * dh), "attn/w_v": (d, hk * dh),
            "attn/q_norm": (dh,), "attn/k_norm": (dh,), "attn/w_g": (d, hk), "attn/b_g": (hk,),
            "attn/w_o": (hq * dh, d), "ffn_norm": (d,), "ffn/w_gate": (d, f), "ffn/w_up": (d, f),
            "ffn/w_down": (f, d)}


def param_shapes(c: Config) -> dict[str, tuple[int, ...]]:
    """The flat parameter dict's keys and shapes."""
    d = c.hidden_size
    out = {"embed/patch": (c.patch * c.patch * 3, d), "embed/token": (c.vocab_size, d),
           "final_norm": (d,), "head": (d, c.vocab_size)}
    for l in range(c.num_hidden_layers):
        out |= {f"layer{l}/{k}": v for k, v in layer_shapes(c).items()}
    return out


def leaf_table(c: Config) -> list[tuple[str, tuple[int, ...], str, tuple[int, ...]]]:
    """(leaf name in an export, its shape, the parameter, its index there): every leaf is a parameter whole."""
    return [(name, shape, name, ()) for name, shape in param_shapes(c).items()]


def init_params(c: Config, seed: int = 0) -> dict[str, np.ndarray]:
    """Seeded float32 weights for a server booted without ``--ckpt`` (tests,
    smoke): gains near one, a gate bias whose memory ``1 / (1 - g)`` is
    log-uniform over 64-4,096 tokens."""
    rs = np.random.Generator(np.random.PCG64(seed))
    out = {}
    for name, shape in param_shapes(c).items():
        kind = name.rsplit("/", 1)[-1]
        if kind in ("norm", "final_norm", "q_norm", "k_norm", "ffn_norm"):
            out[name] = 1.0 + 0.1 * rs.standard_normal(shape)
        elif kind == "b_g":
            out[name] = np.log(np.exp(rs.uniform(np.log(64.0), np.log(4096.0), shape)) - 1.0)
        else:
            fan_in = 1.0 if name == "embed/token" else shape[-2]
            out[name] = rs.standard_normal(shape) / np.sqrt(fan_in)
        out[name] = out[name].astype(np.float32)
    return out


# ------------------------------------------------------------------ the layer

def _qkvg(c: Config, p, n, positions):
    """q [.., 40, 128], k and v [.., 8, 128] in ``n``'s dtype (q and k normed
    a head and rotated), log g [.., 8] float32."""
    heads = lambda y, h: y.reshape(*y.shape[:-1], h, c.head_dim)
    q = rmsnorm(heads(shared.mm(n, p["attn/w_q"]), c.num_attention_heads), p["attn/q_norm"], c.rms_norm_eps)
    k = rmsnorm(heads(shared.mm(n, p["attn/w_k"]), c.num_key_value_heads), p["attn/k_norm"], c.rms_norm_eps)
    v = heads(shared.mm(n, p["attn/w_v"]), c.num_key_value_heads)
    gate = jnp.dot(n, p["attn/w_g"], preferred_element_type=jnp.float32) + p["attn/b_g"].astype(jnp.float32)
    return (mla.rope(q, positions, c.rope_theta), mla.rope(k, positions, c.rope_theta), v,
            jax.nn.log_sigmoid(gate))


def _retention_prefill(c: Config, p, n, lengths, valid):
    """The mixer over whole rows -> (output, (S, z) after each row's last real token)."""
    b, t, _ = n.shape
    g, r, dh = c.num_key_value_heads, c.per, c.head_dim
    q, k, v, log_g = _qkvg(c, p, n, jnp.broadcast_to(jnp.arange(t), (b, t)))
    k = jnp.where(valid[..., None, None], k, 0)                           # padding: no input ...
    log_g = jnp.where(valid[..., None], log_g, 0.0)                        # ... and no decay
    q = q.reshape(b, t, g, r, dh)
    pad = -t % c.chunk_size
    if pad:   # token slots that are no whole chunks (a small canvas): more padding
        q, k, v, log_g = (jnp.pad(a, ((0, 0), (0, pad)) + ((0, 0),) * (a.ndim - 2)) for a in (q, k, v, log_g))
    y, s, z = retention.prefill(q, k, v, log_g, lengths, chunk=c.chunk_size)
    return _out(y[:, :t].reshape(b, t, -1), p["attn/w_o"]), (s, z)


def _retention_step(c: Config, p, n, positions, state):
    """One token a row through the carried (S, z), which come back updated."""
    q, k, v, log_g = _qkvg(c, p, n, positions)
    y, s, z = retention.decode(q.reshape(-1, c.num_key_value_heads, c.per, c.head_dim), k, v, log_g, *state)
    return _out(y.reshape(y.shape[0], -1).astype(n.dtype), p["attn/w_o"]), (s, z)


def _layer(c: Config, p, x, mixer, dtype):
    """``x + mixer(N(x))``, then the SwiGLU: (x, what the mixer hands on)."""
    with jax.named_scope("retention"):
        o, state = mixer(rmsnorm(x, p["norm"], c.rms_norm_eps, dtype))
    x = x + o
    with jax.named_scope("mlp"):
        x = x + shared.dense_ffn(rmsnorm(x, p["ffn_norm"], c.rms_norm_eps, dtype),
                                 p["ffn/w_gate"], p["ffn/w_up"], p["ffn/w_down"])
    return x, state


# ------------------------------------------------------------------ the call

def answer(c: Config, params: dict, tokens, lengths, topk: int):
    """``tokens`` [B, T, patch*patch*3] (normalised pixels, padding slots
    zero), ``lengths`` [B] real tokens a row -> (scores [B, steps, k]
    float32, ids [B, steps, k] int32, counters [len(COUNTERS)] float32)."""
    b, t, _ = tokens.shape
    dtype = params["embed/patch"].dtype
    layers = [shared.layer_params(params, l) for l in range(c.num_hidden_layers)]
    valid = jnp.arange(t)[None, :] < lengths[:, None]
    with jax.named_scope("patches"):
        x = _out(tokens.astype(dtype), params["embed/patch"])

    states = []         # a layer's (S [B, G, d, D], z [B, G, 1, D]) float32
    for p in layers:
        x, state = _layer(c, p, x, lambda n: _retention_prefill(c, p, n, lengths, valid), dtype)
        states.append(state)
    last = jnp.take_along_axis(x, jnp.maximum(lengths - 1, 0)[:, None, None], axis=1)[:, 0]
    top = lambda hidden: shared.top(hidden, params["final_norm"], params["head"], c.rms_norm_eps, topk)
    first = top(last)

    n_tail = c.answer_steps - 1
    out_scores, out_ids = first[0][:, None], first[1][:, None]
    if n_tail:
        def step(carry, s):
            """One more answer step for every row: embed the id the last
            step put first, one token through every layer's state, the head."""
            ids, carried = carry
            new = []
            with jax.named_scope("cached_steps"):
                x1 = params["embed/token"][ids].astype(jnp.float32)
                for p, state in zip(layers, carried):
                    x1, state = _layer(c, p, x1, lambda n: _retention_step(c, p, n, lengths + s, state), dtype)
                    new.append(state)
                scores, top_ids = top(x1)
            return (top_ids[:, 0], new), (scores, top_ids)

        _, (more_scores, more_ids) = jax.lax.scan(step, (first[1][:, 0], states), jnp.arange(n_tail, dtype=jnp.int32))
        out_scores = jnp.concatenate([out_scores, more_scores.transpose(1, 0, 2)], axis=1)
        out_ids = jnp.concatenate([out_ids, more_ids.transpose(1, 0, 2)], axis=1)

    total = {}
    images = (lengths > 0).sum().astype(jnp.float32)
    total["images"], total["tokens_real"] = images, lengths.sum().astype(jnp.float32)
    total["token_slots"] = jnp.float32(b * t)
    total["token_slots_pad"] = total["token_slots"] - total["tokens_real"]
    chunks, skipped = ssd.chunk_counts(lengths, t + (-t % c.chunk_size), c.chunk_size)     # a row's, all heads alike
    total["retention_chunks"] = c.num_hidden_layers * chunks
    total["retention_chunks_skipped"] = c.num_hidden_layers * skipped
    total["answer_steps"], total["answer_steps_cached"] = images * c.answer_steps, images * n_tail
    return out_scores, out_ids, jnp.stack([total[k] for k in COUNTERS])
