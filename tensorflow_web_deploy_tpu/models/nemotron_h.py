"""Nemotron-H's language model (Nemotron 3 Nano) behind the image path: the
second ``task: "generate"`` family of this zoo (models/decoder.py has the
contract).

Every layer is **one mixer behind one RMSNorm**, ``x <- x + mixer(N(x))``,
its kind a character of ``hybrid_override_pattern``:

- ``M``, a Mamba-2 mixer: ``[z | xBC | dt] = W_in n``; ``xBC <-
  silu(conv(xBC))`` (causal, depthwise, ``conv_kernel`` taps, bias); ``xBC``
  is ``x`` (heads x head_dim) | ``B`` | ``C`` (groups x state each);
  ``delta = softplus(dt + dt_bias)``, ``A = -exp(A_log)``; the state-space
  scan (ops/ssd.py); ``y + D x``; the gate, then RMSNorm over each group:
  ``W_out N_g(y * silu(z))``.
- ``*``, grouped-query attention **without positions** (the family's
  attention carries none): ``q, k, v = W_q n, W_k n, W_v n``, causal softmax
  of ``q k' / sqrt(head_dim)``, ``W_o`` (ops/gqa.py).
- ``E``, an expert layer: sigmoid scores over all ``n_routed_experts``, the
  ``num_experts_per_tok`` largest of score + selection bias, weights
  renormalised over the picks and scaled by ``routed_scaling_factor``;
  an expert is ``W_down relu(W_up n)**2``; a shared expert of the same
  shape is always on. The layer is told which experts it holds
  (``experts_held`` from ``experts_held_first``), routes over all of them
  and computes its own experts' part (ops/experts.py).

The vision tower is not modelled: one linear patch embedding stands in for
it (ops/image.py::patch_tokens).

:func:`answer` is what ``jit_serve`` runs after the patches: prefill of all
layers, which leaves **two kinds of state** a row: each attention's keys
and values (a cache that grows a token a step) and each Mamba layer's
float32 recurrent state with its conv tail (fixed size, taken after the
row's last *real* token, not at the row's end); then ``answer_steps - 1``
more steps, one token a row through those states, each embedding the id the
step before put first. Both kinds live and die with the call. It returns
the steps' top-k lists and a vector of counters (``COUNTERS``).

Weights are a flat dict, one array a matrix and one stack of the held
experts a layer (:func:`param_shapes`); :func:`leaf_table` names each leaf
of a ``--ckpt`` export and where it lands.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from ..ops import experts as experts_op
from ..ops import gqa, ssd
from . import decoder as shared
from .decoder import out as _out, rmsnorm

# What `answer` counts, a call: /stats -> batcher.lifecycle.<name>_total sums them over batches. The scan's
# chunks are (rows x Mamba layers) of chunk_size slots, skipped those wholly past their row's length; a step
# is cached when it went one token through the carried states (every step after an image's first).
COUNTERS = ("images", "tokens_real", "token_slots", "token_slots_pad", "picks", "held_picks",
            "held_expert_load_max", "held_expert_load_mean", "ssd_chunks", "ssd_chunks_skipped",
            "answer_steps", "answer_steps_cached")

KINDS = "M*E"


@dataclasses.dataclass(frozen=True)
class Config:
    hidden_size: int = 2688
    hybrid_override_pattern: str = "MEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEMEM*EMEMEMEME"
    mamba_num_heads: int = 64
    mamba_head_dim: int = 64
    ssm_state_size: int = 128
    n_groups: int = 8
    conv_kernel: int = 4
    chunk_size: int = 128
    num_attention_heads: int = 32
    num_key_value_heads: int = 2
    head_dim: int = 128
    moe_intermediate_size: int = 1856
    moe_shared_expert_intermediate_size: int = 3712
    n_routed_experts: int = 128        # the router's outputs, as published
    num_experts_per_tok: int = 6
    routed_scaling_factor: float = 2.5
    experts_held: int = 128            # how many routed experts live here ...
    experts_held_first: int = 0        # ... from this id on
    vocab_size: int = 131072           # rows of embedding and head held here
    layer_norm_epsilon: float = 1e-5
    patch: int = 32
    answer_steps: int = 16
    max_token_slots: int = 16384       # the most token slots (rows x a canvas's tokens) one call may hold

    def __post_init__(self):
        if set(self.hybrid_override_pattern) - set(KINDS):
            raise ValueError(f"hybrid_override_pattern {self.hybrid_override_pattern!r}: a layer is one of {KINDS!r}")

    @classmethod
    def from_dict(cls, d: dict) -> "Config":
        return shared.config_from(cls, d)

    def token_slots(self, canvas_s: int) -> int:
        return (canvas_s // self.patch) ** 2

    @property
    def d_inner(self) -> int:
        return self.mamba_num_heads * self.mamba_head_dim

    @property
    def conv_width(self) -> int:      # x | B | C
        return self.d_inner + 2 * self.n_groups * self.ssm_state_size

    @property
    def q_width(self) -> int:
        return self.num_attention_heads * self.head_dim

    @property
    def kv_width(self) -> int:
        return self.num_key_value_heads * self.head_dim

    @property
    def expert_rows(self) -> int:
        """An expert's hidden width as the stacks hold it: whole blocks of
        128, the columns past ``moe_intermediate_size`` zero (1,856 -> 1,920).
        A stack whose last dimension is no multiple of 128 is copied out
        whole before each product that reads it (0.64 GB a layer at the
        published widths); a zero column adds ``relu(0)**2 = 0``."""
        return -(-self.moe_intermediate_size // 128) * 128

    @property
    def scan_sizes(self) -> dict:
        return dict(heads=self.mamba_num_heads, head_dim=self.mamba_head_dim, groups=self.n_groups)


def layer_shapes(c: Config, kind: str) -> dict[str, tuple[int, ...]]:
    """One layer's parameters by its kind, named without their
    ``layer<l>/`` prefix. A matrix is an array of its own; only the held
    experts are stacked, and the grouped product picks an expert's block by
    index."""
    d, h = c.hidden_size, c.mamba_num_heads
    if kind == "M":
        return {"norm": (d,), "mixer/w_in": (d, c.d_inner + c.conv_width + h),
                "mixer/conv_w": (c.conv_kernel, c.conv_width), "mixer/conv_b": (c.conv_width,),
                "mixer/dt_bias": (h,), "mixer/a_log": (h,), "mixer/d": (h,), "mixer/norm": (c.d_inner,),
                "mixer/w_out": (c.d_inner, d)}
    if kind == "*":
        return {"norm": (d,), "attn/w_q": (d, c.q_width), "attn/w_k": (d, c.kv_width), "attn/w_v": (d, c.kv_width),
                "attn/w_o": (c.q_width, d)}
    f, fs = c.expert_rows, c.moe_shared_expert_intermediate_size
    return {"norm": (d,), "router": (d, c.n_routed_experts), "router_bias": (c.n_routed_experts,),
            "shared/w_up": (d, fs), "shared/w_down": (fs, d),
            "experts/w_up": (c.experts_held, d, f), "experts/w_down": (c.experts_held, f, d)}


def param_shapes(c: Config) -> dict[str, tuple[int, ...]]:
    """The flat parameter dict's keys and shapes."""
    d = c.hidden_size
    out = {"embed/patch": (c.patch * c.patch * 3, d), "embed/token": (c.vocab_size, d),
           "final_norm": (d,), "head": (d, c.vocab_size)}
    for l, kind in enumerate(c.hybrid_override_pattern):
        out |= {f"layer{l}/{k}": v for k, v in layer_shapes(c, kind).items()}
    return out


def leaf_table(c: Config) -> list[tuple[str, tuple[int, ...], str, tuple[int, ...]]]:
    """(leaf name in an export, its shape, the parameter it belongs to, its
    index there): an export names every expert's matrices on their own
    (``layer0/expert3/w_up``) at their published width, the unit it is
    written and read in, and each lands in its layer's stack before the zero
    columns (rows, for ``w_down``); every other leaf is a parameter as it
    stands."""
    d, f = c.hidden_size, c.moe_intermediate_size
    where = {"w_up": ((d, f), (slice(None), slice(0, f))), "w_down": ((f, d), (slice(0, f),))}
    out = []
    for name, shape in param_shapes(c).items():
        layer, _, rest = name.partition("/")
        if rest.startswith("experts/"):
            leaf_shape, index = where[rest[len("experts/"):]]
            out += [(f"{layer}/expert{c.experts_held_first + e}/{rest[len('experts/'):]}", leaf_shape, name, (e, *index))
                    for e in range(shape[0])]
        else:
            out.append((name, shape, name, ()))
    return out


def init_params(c: Config, seed: int = 0) -> dict[str, np.ndarray]:
    """Seeded float32 weights for a server booted without ``--ckpt``
    (tests, smoke): gains near one, steps of 0.001-0.1, decays of 1-16, a
    selection bias of zero."""
    rs = np.random.Generator(np.random.PCG64(seed))
    out = {}
    for name, shape in param_shapes(c).items():
        kind = name.rsplit("/", 1)[-1]
        if kind in ("norm", "final_norm", "d"):
            out[name] = 1.0 + 0.1 * rs.standard_normal(shape)
        elif kind == "dt_bias":       # the inverse softplus of a step drawn log-uniform
            dt = np.exp(rs.uniform(np.log(1e-3), np.log(1e-1), shape))
            out[name] = dt + np.log(-np.expm1(-dt))
        elif kind == "a_log":
            out[name] = np.log(rs.uniform(1.0, 16.0, shape))
        elif kind == "conv_b":
            out[name] = 0.1 * rs.standard_normal(shape)
        elif kind == "router_bias":
            out[name] = np.zeros(shape)
        else:
            fan_in = 1.0 if name == "embed/token" else shape[-2]
            out[name] = rs.standard_normal(shape) / np.sqrt(fan_in)
        out[name] = out[name].astype(np.float32)
    for name in out:      # the stacks' columns (rows) past the published width
        if name.endswith("experts/w_up"):
            out[name][..., c.moe_intermediate_size:] = 0.0
        elif name.endswith("experts/w_down"):
            out[name][:, c.moe_intermediate_size:] = 0.0
    return out


# ------------------------------------------------------------------ the mixers

def _mixer_inputs(c: Config, p, n):
    """z (in ``n``'s dtype), xBC before its conv and the step delta, float32."""
    proj = jnp.dot(n, p["mixer/w_in"], preferred_element_type=jnp.float32)
    z, xbc, dt = jnp.split(proj, [c.d_inner, c.d_inner + c.conv_width], axis=-1)
    return z.astype(n.dtype), xbc, jax.nn.softplus(dt + p["mixer/dt_bias"].astype(jnp.float32))


def _gated_out(c: Config, p, y, x, z, dtype):
    """``W_out N_g((y + D x) * silu(z))``: the gate first, then RMSNorm over
    each group's values. ``y``, ``x`` [.., H*P]."""
    f32 = jnp.float32
    d_x = jnp.repeat(p["mixer/d"].astype(f32), c.mamba_head_dim) * x.astype(f32)
    gated = (y.astype(f32) + d_x) * jax.nn.silu(z.astype(f32))
    grouped = gated.reshape(*gated.shape[:-1], c.n_groups, -1)
    normed = rmsnorm(grouped, p["mixer/norm"].reshape(c.n_groups, -1), c.layer_norm_epsilon, dtype)
    return _out(normed.reshape(gated.shape), p["mixer/w_out"])


def _mixer_prefill(c: Config, p, n, lengths, valid):
    """The mixer over whole rows -> (output, (state after each row's last
    real token, its conv tail))."""
    t, chunk = n.shape[1], c.chunk_size
    z, xbc, dt = _mixer_inputs(c, p, n)
    xbc, tail = ssd.causal_conv(xbc, p["mixer/conv_w"], p["mixer/conv_b"], lengths)
    xbc = jax.nn.silu(xbc).astype(n.dtype)
    dt = jnp.where(valid[..., None], dt, 0.0)                              # padding: decay 1, input 0
    pad = -t % chunk
    if pad:   # token slots that are no whole chunks (a small canvas): more padding
        xbc, dt = (jnp.pad(a, ((0, 0), (0, pad), (0, 0))) for a in (xbc, dt))
    a = -jnp.exp(p["mixer/a_log"].astype(jnp.float32))
    y, state = ssd.scan(xbc, dt, a, lengths, chunk=chunk, **c.scan_sizes)
    return _gated_out(c, p, y[:, :t], xbc[:, :t, :c.d_inner], z, n.dtype), (state, tail)


def _mixer_step(c: Config, p, n, state, tail):
    """One token a row from the carried state and conv tail."""
    z, xbc, dt = _mixer_inputs(c, p, n)
    a = -jnp.exp(p["mixer/a_log"].astype(jnp.float32))
    y, x, state, tail = ssd.ssm_step(xbc, dt, a, state, tail, p["mixer/conv_w"], p["mixer/conv_b"], **c.scan_sizes)
    return _gated_out(c, p, y, x, z, n.dtype), (state, tail)


def _qkv(c: Config, p, n):
    """q [.., Hq, d], k and v [.., Hk, d] in ``n``'s dtype; no positions."""
    heads = lambda y, h: y.reshape(*y.shape[:-1], h, c.head_dim)
    return (heads(shared.mm(n, p["attn/w_q"]), c.num_attention_heads),
            heads(shared.mm(n, p["attn/w_k"]), c.num_key_value_heads),
            heads(shared.mm(n, p["attn/w_v"]), c.num_key_value_heads))


def _attn_prefill(c: Config, p, n, lengths):
    b, t, _ = n.shape
    g, r = c.num_key_value_heads, c.num_attention_heads // c.num_key_value_heads
    q, k, v = _qkv(c, p, n)
    q = q.reshape(b, t, g, r, c.head_dim).transpose(0, 2, 3, 1, 4)
    k, v = k.transpose(0, 2, 1, 3), v.transpose(0, 2, 1, 3)                # [B, G, T, d]: as the cache keeps them
    o = gqa.core(q, k, v, lengths, 1.0 / float(np.sqrt(c.head_dim)))
    return _out(o.transpose(0, 3, 1, 2, 4).reshape(b, t, c.q_width), p["attn/w_o"]), (k, v)


def _attn_step(c: Config, p, n, lengths, cache, tail, step):
    """One token a row: its key and value go into ``tail`` at ``step``, then
    attention over the image's cache and the tail so far."""
    g, r = c.num_key_value_heads, c.num_attention_heads // c.num_key_value_heads
    q, k, v = _qkv(c, p, n[:, None, :])
    tail_k = jax.lax.dynamic_update_slice_in_dim(tail[0], k.transpose(0, 2, 1, 3), step, 2)
    tail_v = jax.lax.dynamic_update_slice_in_dim(tail[1], v.transpose(0, 2, 1, 3), step, 2)
    o = gqa.decode_step(q[:, 0].reshape(-1, g, r, c.head_dim), cache[0], cache[1], tail_k, tail_v, lengths,
                        step + 1, 1.0 / float(np.sqrt(c.head_dim)))
    return _out(o.reshape(-1, c.q_width).astype(n.dtype), p["attn/w_o"]), (tail_k, tail_v)


def _moe(c: Config, p, n, valid):
    """The routed experts held here and the shared one, on ``n`` of any leading shape."""
    flat = n.reshape(-1, n.shape[-1])
    m, counters = experts_op.expert_layer(
        flat, valid.reshape(-1), p["router"], p["experts/w_up"], p["experts/w_down"],
        topk=c.num_experts_per_tok, scale=c.routed_scaling_factor, n_routed=c.n_routed_experts,
        held_first=c.experts_held_first, router="sigmoid", router_bias=p["router_bias"])
    with jax.named_scope("shared_expert"):
        m = m + experts_op.relu2(flat, p["shared/w_up"], p["shared/w_down"])
    return m.reshape(n.shape), counters


# ------------------------------------------------------------------ the call

def answer(c: Config, params: dict, tokens, lengths, topk: int):
    """``tokens`` [B, T, patch*patch*3] (normalised pixels, padding slots
    zero), ``lengths`` [B] real tokens a row -> (scores [B, steps, k]
    float32, ids [B, steps, k] int32, counters [len(COUNTERS)] float32)."""
    b, t, _ = tokens.shape
    dtype = params["embed/patch"].dtype
    layers = [(kind, shared.layer_params(params, l)) for l, kind in enumerate(c.hybrid_override_pattern)]
    norm = lambda p, x: rmsnorm(x, p["norm"], c.layer_norm_epsilon, dtype)
    valid = jnp.arange(t)[None, :] < lengths[:, None]
    real_row = lengths > 0
    with jax.named_scope("patches"):
        x = _out(tokens.astype(dtype), params["embed/patch"])

    total = dict.fromkeys(COUNTERS, jnp.float32(0))
    left = []       # a layer: (keys, values) [B, G, T, d] | (state [B, H, P, N], conv tail [B, taps-1, C]) | None
    for kind, p in layers:
        if kind == "M":
            with jax.named_scope("mamba"):
                o, behind = _mixer_prefill(c, p, norm(p, x), lengths, valid)
        elif kind == "*":
            with jax.named_scope("attention"):
                o, behind = _attn_prefill(c, p, norm(p, x), lengths)
        else:
            (o, counters), behind = _moe(c, p, norm(p, x), valid), None
            total |= {k: total[k] + v for k, v in counters.items() if k in total}
        x = x + o
        left.append(behind)
    last = jnp.take_along_axis(x, jnp.maximum(lengths - 1, 0)[:, None, None], axis=1)[:, 0]
    top = lambda hidden: shared.top(hidden, params["final_norm"], params["head"], c.layer_norm_epsilon, topk)
    first = top(last)

    n_tail = c.answer_steps - 1
    out_scores, out_ids = first[0][:, None], first[1][:, None]
    if n_tail:
        tail_shape = (b, c.num_key_value_heads, n_tail, c.head_dim)
        carried0 = [behind if kind == "M" else (jnp.zeros(tail_shape, dtype), jnp.zeros(tail_shape, dtype))
                    if kind == "*" else None for (kind, _), behind in zip(layers, left)]

        def step(carry, s):
            """One more answer step for every row: embed the id the last
            step put first, one token through every layer's state, the head."""
            ids, carried, picks = carry
            new = []
            with jax.named_scope("cached_steps"):
                x1 = params["embed/token"][ids].astype(jnp.float32)
                for (kind, p), behind, state in zip(layers, left, carried):
                    if kind == "M":
                        with jax.named_scope("mamba"):
                            o, state = _mixer_step(c, p, norm(p, x1), *state)
                    elif kind == "*":
                        with jax.named_scope("attention"):
                            o, state = _attn_step(c, p, norm(p, x1), lengths, behind, state, s)
                    else:
                        o, counters = _moe(c, p, norm(p, x1), real_row)
                        picks = {k: picks[k] + counters[k] for k in picks}
                    x1 = x1 + o
                    new.append(state)
                scores, top_ids = top(x1)
            return (top_ids[:, 0], new, picks), (scores, top_ids)

        picks0 = {k: total[k] for k in ("picks", "held_picks")}
        (_, _, picks), (more_scores, more_ids) = jax.lax.scan(
            step, (first[1][:, 0], carried0, picks0), jnp.arange(n_tail, dtype=jnp.int32))
        total |= picks
        out_scores = jnp.concatenate([out_scores, more_scores.transpose(1, 0, 2)], axis=1)
        out_ids = jnp.concatenate([out_ids, more_ids.transpose(1, 0, 2)], axis=1)

    images = real_row.sum().astype(jnp.float32)
    total["images"], total["tokens_real"] = images, lengths.sum().astype(jnp.float32)
    total["token_slots"] = jnp.float32(b * t)
    total["token_slots_pad"] = total["token_slots"] - total["tokens_real"]
    n_mamba = float(c.hybrid_override_pattern.count("M"))
    chunks, skipped = ssd.chunk_counts(lengths, t + (-t % c.chunk_size), c.chunk_size)
    total["ssd_chunks"], total["ssd_chunks_skipped"] = n_mamba * chunks, n_mamba * skipped
    total["answer_steps"], total["answer_steps_cached"] = images * c.answer_steps, images * n_tail
    return out_scores, out_ids, jnp.stack([total[k] for k in COUNTERS])
