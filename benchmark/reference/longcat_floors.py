"""Floors of ``reference/longcat.py``'s model: what ``cost.py`` asks of a
``floors.module`` (:func:`image_flops`, :func:`serve_bytes`, per row of the
window's padding table: ``canvas``, ``batch_bucket``, ``batches``,
``rows_real``, ``rows_dispatched``, ``px_real``), and for each kernel the
program names in a device trace its operations and bytes a call
(:func:`kernel_floor`; ``readers/kernel_roofline.py`` divides by its time).

A row's real pixels are its tokens: ``px_real / rows_real / patch**2`` a
real image (the mix's sides are multiples of the patch). Counted is only
what no implementation can avoid, per *real* image:

- matmuls: two operations a parameter a token touches: the patch
  embedding, each layer's two latent attentions (down- and up-projections
  of queries, keys and values, the output projection), its two dense FFNs,
  its router, and of the held experts the share a uniform router sends a
  token to (``moe_topk * experts_held / (n_routed_experts +
  zero_expert_num)`` picks a token: a quarter of one here). The zero
  experts cost one multiply-add a value and are left out;
- the attention core: causal, so half of ``T**2`` scores a head, each 192
  multiply-adds for the score and 128 for the value. ``T`` is the row's
  *mean* tokens: the mean of squares is never below the square of the mean,
  so a row of mixed sizes is undercounted and a share never reads over 100%
  for this reason;
- the answer steps after the first: the same parameters for one token a
  step, with the keys and values absorbed (a score costs 576 multiply-adds
  a head and a cached token, the weighted sum 512), and the head over the
  vocabulary slice at every step.

Bytes of a call: every parameter outside the experts once for the prefill
and once more for each further step (a step cannot start before the one
before it has ended); of the held experts as many as the call's tokens can
reach, a step as a prefill; the latent cache written once and read a step;
pixels in, answers out. Activations are not counted.

``serving/costmodel.py`` has the same counts for the server's own
``/stats``; a test holds the two equal.
"""

from __future__ import annotations

DTYPE_BYTES = {"bfloat16": 2, "float32": 4}


def tokens(model: dict, row: dict) -> float:
    """Mean tokens of a real image of ``row``."""
    return row["px_real"] / max(row["rows_real"], 1) / model["patch"] ** 2


def mla_params(m: dict) -> int:
    d, h = m["hidden_size"], m["num_attention_heads"]
    dq = m["qk_nope_head_dim"] + m["qk_rope_head_dim"]
    return (d * m["q_lora_rank"] + m["q_lora_rank"] * h * dq + d * (m["kv_lora_rank"] + m["qk_rope_head_dim"])
            + m["kv_lora_rank"] * h * (m["qk_nope_head_dim"] + m["v_head_dim"]) + h * m["v_head_dim"] * d)


def ffn_params(m: dict) -> int:
    return 3 * m["hidden_size"] * m["ffn_hidden_size"]


def router_params(m: dict) -> int:
    return m["hidden_size"] * (m["n_routed_experts"] + m["zero_expert_num"])


def expert_params(m: dict) -> int:
    return 3 * m["hidden_size"] * m["expert_ffn_hidden_size"]


def held_picks_per_token(m: dict) -> float:
    """Picks a token that a uniform router sends to the experts held here."""
    return m["moe_topk"] * m["experts_held"] / (m["n_routed_experts"] + m["zero_expert_num"])


def layer_macs_per_token(m: dict) -> float:
    """Multiply-adds of one token in one layer's matrices (no attention core)."""
    return 2 * mla_params(m) + 2 * ffn_params(m) + router_params(m) + held_picks_per_token(m) * expert_params(m)


def core_macs(m: dict, t: float) -> float:
    """The causal core of one attention over ``t`` tokens."""
    per_pair = m["qk_nope_head_dim"] + m["qk_rope_head_dim"] + m["v_head_dim"]
    return m["num_attention_heads"] * t * t / 2 * per_pair


def absorbed_macs(m: dict, t: float) -> float:
    """One new token against ``t`` cached latents, one attention."""
    return m["num_attention_heads"] * t * (2 * m["kv_lora_rank"] + m["qk_rope_head_dim"])


def dense_params(m: dict) -> int:
    """Every parameter a call reads whatever its tokens: outside the experts
    and the token embedding."""
    d = m["hidden_size"]
    return (m["patch"] ** 2 * 3 * d + d * m["vocab_size"]
            + m["num_layers"] * (2 * mla_params(m) + 2 * ffn_params(m) + router_params(m)))


def image_flops(model: dict, row: dict) -> float:
    m, t, more = model, tokens(model, row), model["answer_steps"] - 1
    prefill = t * (m["patch"] ** 2 * 3 * m["hidden_size"] + m["num_layers"] * layer_macs_per_token(m)) \
        + m["num_layers"] * 2 * core_macs(m, t)
    steps = more * m["num_layers"] * (layer_macs_per_token(m) + 2 * absorbed_macs(m, t))
    head = m["answer_steps"] * m["hidden_size"] * m["vocab_size"]
    return 2.0 * (prefill + steps + head)


def experts_reached(m: dict, call_tokens: float) -> float:
    """Held experts (of one layer) that this many tokens can reach."""
    return min(float(m["experts_held"]), held_picks_per_token(m) * call_tokens)


def serve_bytes(model: dict, row: dict) -> float:
    m, b = model, DTYPE_BYTES[model["dtype"]]
    rows = row["rows_real"] / row["batches"]
    t, more = tokens(m, row), m["answer_steps"] - 1
    latents = m["num_layers"] * 2 * (m["kv_lora_rank"] + m["qk_rope_head_dim"])          # values a token in the cache
    params = (1 + more) * dense_params(m) + m["num_layers"] * expert_params(m) * (
        experts_reached(m, rows * t) + more * experts_reached(m, rows))
    return (b * params + b * rows * t * latents * (1 + more) + b * rows * more * m["hidden_size"]
            + row["px_real"] / row["batches"] * 3 + rows * m["answer_steps"] * m["topk"] * 8)


def kernel_floor(model: dict, row: dict, kernel: str) -> tuple[float, float] | None:
    """(operations, bytes) that every call of ``kernel`` in the mean serve
    call of ``row`` has to do and move, summed over the call's calls of it;
    None for a name this model has no kernel of."""
    m, b = model, DTYPE_BYTES[model["dtype"]]
    rows, t = row["rows_real"] / row["batches"], tokens(model, row)
    h, n_attn = m["num_attention_heads"], m["num_layers"] * 2
    if kernel == "mla_prefill":
        per_token = h * (2 * (m["qk_nope_head_dim"] + m["v_head_dim"]) + m["qk_rope_head_dim"]) + m["qk_rope_head_dim"]
        return 2.0 * n_attn * rows * core_macs(m, t), float(b * n_attn * rows * t * per_token)
    if kernel == "expert_gmm":
        # The kernel fetches no block of an expert that no token of the call picked, and which experts a call's
        # tokens pick is the router's to say (a page's patches pick alike: a batch's busiest held expert had 5-8
        # times the mean on the chip). So of the weights only what any routing has to read is a floor: one
        # expert's matrices wherever the call has a held pick at all, not all that its tokens could reach.
        more = m["answer_steps"] - 1
        picks = held_picks_per_token(m) * rows * (t + more)
        reached = min(1.0, held_picks_per_token(m) * rows * t) + more * min(1.0, held_picks_per_token(m) * rows)
        moved = picks * (2 * m["hidden_size"] + 3 * m["expert_ffn_hidden_size"])      # rows in, hidden twice out and once in, rows out
        return 2.0 * m["num_layers"] * picks * expert_params(m), float(b * m["num_layers"] * (reached * expert_params(m) + moved))
    return None
