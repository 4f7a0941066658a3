"""Floors of ``reference/nemotron_h.py``'s model: what ``cost.py`` asks of a
``floors.module`` (:func:`image_flops`, :func:`serve_bytes`, per row of the
window's padding table: ``canvas``, ``batch_bucket``, ``batches``,
``rows_real``, ``rows_dispatched``, ``px_real``), and for each kernel the
program names in a device trace its operations and bytes a call
(:func:`kernel_floor`; ``readers/kernel_roofline.py`` divides by its time).

A row's real pixels are its tokens: ``px_real / rows_real / patch**2`` a real
image. Counted is only what no implementation can avoid, per *real* image,
never a padding slot's:

- matmuls: two operations a parameter a token touches: the patch embedding,
  a Mamba layer's in and out projections, an attention layer's four
  matrices, an expert layer's router, its shared expert and of its held
  experts the share a uniform router sends a token to
  (``num_experts_per_tok * experts_held / n_routed_experts`` picks a token:
  3 here);
- the scan, a token a Mamba layer, in its chunked form at the published
  chunk of 128 (the cheaper of the two forms: the token-by-token recurrence
  costs three to four operations a state value): per head two products
  against the [64, 128] state (read-out, update) and *half* a chunk's masked
  product, per group *half* a chunk's ``C B'``: 1.38 M multiply-adds. A
  kernel that forms whole chunk squares does twice the masked part and reads
  under 100% at its best: the floor errs low. The conv, the gate, the norms
  and the decays are elementwise and left out;
- the attention core: causal, so half of ``T**2`` scores a query head, each
  128 multiply-adds for the score and 128 for the value. ``T`` is the row's
  *mean* tokens: the mean of squares is never below the square of the mean,
  so a row of mixed sizes is undercounted and no share reads over 100% for
  this reason;
- the answer steps after the first: the same matrices for one token a step,
  the recurrence on the state (update and read-out, a multiply-add each a
  state value), the new token's attention against every cached key and
  value, and the head over the vocabulary slice at every step.

Bytes of a call: every parameter outside the experts once for the prefill
and once more for each further step (a step cannot start before the one
before it has ended), the patch embedding once; of the held experts as many
as the call's tokens can reach, a step as a prefill; the key/value cache
written once and read a step; each row's recurrent state and conv tail
(float32) written once, then read and written a step; a token's embedding
row a step; pixels in, answers out. Activations are not counted.

``serving/costmodel.py`` has the same counts for the server's own
``/stats``; a test holds the two equal.
"""

from __future__ import annotations

DTYPE_BYTES = {"bfloat16": 2, "float32": 4}


def tokens(model: dict, row: dict) -> float:
    """Mean tokens of a real image of ``row``."""
    return row["px_real"] / max(row["rows_real"], 1) / model["patch"] ** 2


def layers(m: dict) -> dict[str, int]:
    """How many layers of each kind the pattern has."""
    return {k: m["hybrid_override_pattern"].count(k) for k in "M*E"}


def widths(m: dict) -> dict[str, int]:
    inner, bc = m["mamba_num_heads"] * m["mamba_head_dim"], m["n_groups"] * m["ssm_state_size"]
    return {"inner": inner, "bc": bc, "conv": inner + 2 * bc,
            "q": m["num_attention_heads"] * m["head_dim"], "kv": m["num_key_value_heads"] * m["head_dim"]}


def mamba_params(m: dict) -> int:
    """A Mamba layer's two matrices (its conv, gains and per-head scalars are elementwise)."""
    w = widths(m)
    return m["hidden_size"] * (w["inner"] + w["conv"] + m["mamba_num_heads"]) + w["inner"] * m["hidden_size"]


def attn_params(m: dict) -> int:
    w = widths(m)
    return m["hidden_size"] * (w["q"] + 2 * w["kv"]) + w["q"] * m["hidden_size"]


def router_params(m: dict) -> int:
    return m["hidden_size"] * m["n_routed_experts"]


def shared_params(m: dict) -> int:
    return 2 * m["hidden_size"] * m["moe_shared_expert_intermediate_size"]


def expert_params(m: dict) -> int:
    return 2 * m["hidden_size"] * m["moe_intermediate_size"]


def held_picks_per_token(m: dict) -> float:
    """Picks a token that a uniform router sends to the experts held here."""
    return m["num_experts_per_tok"] * m["experts_held"] / m["n_routed_experts"]


def scan_macs_per_token(m: dict) -> int:
    """One Mamba layer's chunked scan, a token."""
    q, p, n = m["chunk_size"], m["mamba_head_dim"], m["ssm_state_size"]
    return m["mamba_num_heads"] * (2 * p * n + q * p // 2) + m["n_groups"] * (q * n // 2)


def step_macs_per_token(m: dict) -> int:
    """One Mamba layer's recurrence for one token: the state's update and its read-out."""
    return 2 * m["mamba_num_heads"] * m["mamba_head_dim"] * m["ssm_state_size"]


def core_macs(m: dict, t: float) -> float:
    """The causal core of one attention layer over ``t`` tokens."""
    return m["num_attention_heads"] * t * t / 2 * 2 * m["head_dim"]


def decode_macs(m: dict, t: float) -> float:
    """One new token against ``t`` cached keys and values, one attention layer."""
    return m["num_attention_heads"] * t * 2 * m["head_dim"]


def matrix_macs_per_token(m: dict) -> float:
    """Multiply-adds of one token in every layer's matrices (no scan, no attention core)."""
    n = layers(m)
    return (n["M"] * mamba_params(m) + n["*"] * attn_params(m)
            + n["E"] * (router_params(m) + shared_params(m) + held_picks_per_token(m) * expert_params(m)))


def dense_params(m: dict) -> int:
    """Every parameter a call reads whatever its tokens: outside the routed
    experts and the token embedding."""
    n, d = layers(m), m["hidden_size"]
    return (m["patch"] ** 2 * 3 * d + d * m["vocab_size"] + n["M"] * mamba_params(m) + n["*"] * attn_params(m)
            + n["E"] * (router_params(m) + shared_params(m)))


def param_count(m: dict, patch_embedding: bool = True) -> int:
    """Every parameter, the vectors too. ``patch_embedding=False`` counts the
    language model as published (the stand-in for the vision tower is ours)."""
    n, d, w, h = layers(m), m["hidden_size"], widths(m), m["mamba_num_heads"]
    small_m = m["conv_kernel"] * w["conv"] + w["conv"] + 3 * h + w["inner"] + d
    outer = 2 * d * m["vocab_size"] + d + (m["patch"] ** 2 * 3 * d if patch_embedding else 0)
    return (outer + n["M"] * (mamba_params(m) + small_m) + n["*"] * (attn_params(m) + d)
            + n["E"] * (router_params(m) + shared_params(m) + m["experts_held"] * expert_params(m)
                        + m["n_routed_experts"] + d))


def published(m: dict) -> dict:
    """The model block with the published counts in place of the held ones."""
    return {**m, **m["published"], "experts_held": m["published"]["n_routed_experts"]}


def image_flops(model: dict, row: dict) -> float:
    m, t, more, n = model, tokens(model, row), model["answer_steps"] - 1, layers(model)
    prefill = (t * (m["patch"] ** 2 * 3 * m["hidden_size"] + matrix_macs_per_token(m) + n["M"] * scan_macs_per_token(m))
               + n["*"] * core_macs(m, t))
    steps = more * (matrix_macs_per_token(m) + n["M"] * step_macs_per_token(m) + n["*"] * decode_macs(m, t))
    head = m["answer_steps"] * m["hidden_size"] * m["vocab_size"]
    return 2.0 * (prefill + steps + head)


def experts_reached(m: dict, call_tokens: float) -> float:
    """Held experts (of one layer) that this many tokens can reach."""
    return min(float(m["experts_held"]), held_picks_per_token(m) * call_tokens)


def state_values(m: dict) -> int:
    """What a row's answer steps carry a Mamba layer: the recurrent state and the conv tail, float32."""
    return (m["mamba_num_heads"] * m["mamba_head_dim"] * m["ssm_state_size"]
            + (m["conv_kernel"] - 1) * widths(m)["conv"])


def serve_bytes(model: dict, row: dict) -> float:
    m, b, n = model, DTYPE_BYTES[model["dtype"]], layers(model)
    rows = row["rows_real"] / row["batches"]
    t, more, d = tokens(m, row), m["answer_steps"] - 1, m["hidden_size"]
    params = ((1 + more) * dense_params(m) - more * m["patch"] ** 2 * 3 * d
              + n["E"] * expert_params(m) * (experts_reached(m, rows * t) + more * experts_reached(m, rows)))
    kv = n["*"] * 2 * widths(m)["kv"]                                                   # values a token in the cache
    return (b * params + b * rows * t * kv * (1 + more) + 4 * rows * n["M"] * state_values(m) * (1 + 2 * more)
            + b * rows * more * d + row["px_real"] / row["batches"] * 3 + rows * m["answer_steps"] * m["topk"] * 8)


def kernel_floor(model: dict, row: dict, kernel: str) -> tuple[float, float] | None:
    """(operations, bytes) that every call of ``kernel`` in the mean serve
    call of ``row`` has to do and move for the row's *real* tokens, summed
    over the call's calls of it; None for a name this model has no kernel of."""
    m, b, n = model, DTYPE_BYTES[model["dtype"]], layers(model)
    rows, t = row["rows_real"] / row["batches"], tokens(model, row)
    w = widths(m)
    if kernel == "ssd_prefill":
        # in: x, B, C of every real token once, its step and its running sum; out: y, and each row's state once
        per_token = b * (2 * w["inner"] + 2 * w["bc"]) + 4 * 2 * m["mamba_num_heads"]
        state = 4 * m["mamba_num_heads"] * m["mamba_head_dim"] * m["ssm_state_size"]
        return 2.0 * n["M"] * rows * t * scan_macs_per_token(m), float(n["M"] * rows * (t * per_token + state))
    if kernel == "gqa_prefill":
        per_token = b * m["head_dim"] * (2 * m["num_attention_heads"] + 2 * m["num_key_value_heads"])
        return 2.0 * n["*"] * rows * core_macs(m, t), float(n["*"] * rows * t * per_token)
    if kernel == "expert_gmm":
        # As longcat_floors.py: which experts a call's tokens pick is the router's to say, so of the weights only
        # what any routing has to read is a floor: one expert's matrices wherever the call has a held pick at all.
        more = m["answer_steps"] - 1
        picks = held_picks_per_token(m) * rows * (t + more)
        reached = min(1.0, held_picks_per_token(m) * rows * t) + more * min(1.0, held_picks_per_token(m) * rows)
        moved = picks * (2 * m["hidden_size"] + 2 * m["moe_intermediate_size"])       # rows in, hidden out and in, rows out
        return 2.0 * n["E"] * picks * expert_params(m), float(b * n["E"] * (reached * expert_params(m) + moved))
    return None
