"""Floors of ``reference/brumby.py``'s model: what ``cost.py`` asks of a
``floors.module`` (:func:`image_flops`, :func:`serve_bytes`, per row of the
window's padding table: ``canvas``, ``batch_bucket``, ``batches``,
``rows_real``, ``rows_dispatched``, ``px_real``), and for each kernel the
program names in a device trace its operations and bytes a call
(:func:`kernel_floor`; ``readers/kernel_roofline.py`` divides by its time).

A row's real pixels are its tokens: ``px_real / rows_real / patch**2`` a real
image. Counted is only what no implementation can avoid, per *real* image,
never a padding slot's, with the **minimal** symmetric feature map of
``D = d (d + 1) / 2`` products (8,256 at ``d`` 128; the program's staircase
of whole tiles has 8,704, which reads as roofline lost):

- matmuls: two operations a parameter a token touches: the patch embedding,
  a layer's query, key, value, gate and output matrices and its SwiGLU;
- the retention core of a prefill, a token a layer, the lesser of two forms
  (``retention_macs_per_token``): the **chunked** form at the chunk of 128
  (per query head the ``D x d`` read-out against its state and half a
  chunk's masked products, a score and a value each; per key/value head the
  state's ``D x d`` update), or the **attention** form over the row's ``t``
  tokens (per query head half the row's scores and weighted values; per
  key/value head the final state once, ``D x d``). At the pages' lengths
  the attention form is the lesser (24 M multiply-adds a token at 3,072,
  where the chunked form is 51 M), and ``t`` is the row's *mean* tokens: the
  mean of squares is never below the square of the mean, so a row of mixed
  sizes is undercounted. The norms, rotary, gates and decays are
  elementwise and left out;
- the answer steps after the first: the same matrices for one token a step,
  the recurrence (per query head the read-out, per key/value head the
  update: ``D x d`` each), the head over the vocabulary at every step.

Bytes of a call: every parameter but the token embedding once for the
prefill and once more for each further step (a step cannot start before the
one before it has ended), the patch embedding once; each row's state and
normaliser (``Hk x D x (d + 1)`` float32 a layer) written once, then read and
written a step; a token's embedding row a step; pixels in, answers out.
Activations are not counted.

``serving/costmodel.py`` has the same counts for the server's own
``/stats``; a test holds the two equal.
"""

from __future__ import annotations

DTYPE_BYTES = {"bfloat16": 2, "float32": 4}


def tokens(model: dict, row: dict) -> float:
    """Mean tokens of a real image of ``row``."""
    return row["px_real"] / max(row["rows_real"], 1) / model["patch"] ** 2


def features(m: dict) -> int:
    """``D``: the minimal symmetric degree-2 map of a head of ``head_dim``."""
    return m["head_dim"] * (m["head_dim"] + 1) // 2


def attn_params(m: dict) -> int:
    """A layer's query, key, value and output matrices."""
    d, dh = m["hidden_size"], m["head_dim"]
    return d * (m["num_attention_heads"] + 2 * m["num_key_value_heads"]) * dh + m["num_attention_heads"] * dh * d


def gate_params(m: dict) -> int:
    return m["hidden_size"] * m["num_key_value_heads"]


def ffn_params(m: dict) -> int:
    return 3 * m["hidden_size"] * m["intermediate_size"]


def matrix_macs_per_token(m: dict) -> int:
    """Multiply-adds of one token in one layer's matrices."""
    return attn_params(m) + gate_params(m) + ffn_params(m)


def chunked_macs_per_token(m: dict) -> float:
    """One layer's retention core, a token, in the chunked form."""
    dh, big = m["head_dim"], features(m)
    return m["num_attention_heads"] * (big * dh + m["chunk_size"] / 2 * 2 * dh) + m["num_key_value_heads"] * big * dh


def attention_macs_per_token(m: dict, t: float) -> float:
    """One layer's retention core, a token of a row of ``t``, in the attention form."""
    dh = m["head_dim"]
    return m["num_attention_heads"] * t / 2 * 2 * dh + m["num_key_value_heads"] * features(m) * dh


def retention_macs_per_token(m: dict, t: float) -> float:
    return min(chunked_macs_per_token(m), attention_macs_per_token(m, t))


def step_macs(m: dict) -> int:
    """One token through one layer's state: every query head's read-out and every key/value head's update."""
    return features(m) * m["head_dim"] * (m["num_attention_heads"] + m["num_key_value_heads"])


def state_values(m: dict) -> int:
    """What a row's answer steps carry a layer: the state and the normaliser, float32."""
    return m["num_key_value_heads"] * features(m) * (m["head_dim"] + 1)


def dense_params(m: dict) -> int:
    """Every parameter a call reads but the token embedding (the norms left out)."""
    d = m["hidden_size"]
    return m["patch"] ** 2 * 3 * d + d * m["vocab_size"] + m["num_hidden_layers"] * matrix_macs_per_token(m)


def param_count(m: dict, patch_embedding: bool = True) -> int:
    """Every parameter, the vectors too. ``patch_embedding=False`` counts the
    language model as published (the stand-in for the vision tower is ours)."""
    d = m["hidden_size"]
    small = 2 * d + 2 * m["head_dim"] + m["num_key_value_heads"]            # two norms, q and k norms, the gate's bias
    outer = 2 * d * m["vocab_size"] + d + (m["patch"] ** 2 * 3 * d if patch_embedding else 0)
    return outer + m["num_hidden_layers"] * (matrix_macs_per_token(m) + small)


def published(m: dict) -> dict:
    """The model block with the published counts in place of the held ones."""
    return {**m, **m["published"]}


def image_flops(model: dict, row: dict) -> float:
    m, t, more, layers = model, tokens(model, row), model["answer_steps"] - 1, model["num_hidden_layers"]
    prefill = t * (m["patch"] ** 2 * 3 * m["hidden_size"]
                   + layers * (matrix_macs_per_token(m) + retention_macs_per_token(m, t)))
    steps = more * layers * (matrix_macs_per_token(m) + step_macs(m))
    head = m["answer_steps"] * m["hidden_size"] * m["vocab_size"]
    return 2.0 * (prefill + steps + head)


def serve_bytes(model: dict, row: dict) -> float:
    m, b = model, DTYPE_BYTES[model["dtype"]]
    rows = row["rows_real"] / row["batches"]
    more, d = m["answer_steps"] - 1, m["hidden_size"]
    params = (1 + more) * dense_params(m) - more * m["patch"] ** 2 * 3 * d
    states = 4 * rows * m["num_hidden_layers"] * state_values(m) * (1 + 2 * more)
    return (b * params + states + b * rows * more * d + row["px_real"] / row["batches"] * 3
            + rows * m["answer_steps"] * m["topk"] * 8)


def kernel_floor(model: dict, row: dict, kernel: str) -> tuple[float, float] | None:
    """(operations, bytes) that every call of ``kernel`` in the mean serve
    call of ``row`` has to do and move for the row's *real* tokens, summed
    over the call's calls of it; None for a name this model has no kernel of."""
    m, b, layers = model, DTYPE_BYTES[model["dtype"]], model["num_hidden_layers"]
    rows, t = row["rows_real"] / row["batches"], tokens(model, row)
    hq, hk, dh = m["num_attention_heads"], m["num_key_value_heads"], m["head_dim"]
    if kernel == "retention_prefill":
        # in: q, k, v of every real token once; out: y, and each row's state and normaliser once
        moved = rows * (t * b * (2 * hq + 2 * hk) * dh + 4 * state_values(m))
        return 2.0 * layers * rows * t * retention_macs_per_token(m, t), float(layers * moved)
    if kernel == "retention_step":
        # a row, layer and step: its state and normaliser read and written once, the token's q, k, v in, y out
        more = m["answer_steps"] - 1
        moved = 2 * 4 * state_values(m) + b * (2 * hq + 2 * hk) * dh
        return 2.0 * more * layers * rows * step_macs(m), float(more * layers * rows * moved)
    return None
