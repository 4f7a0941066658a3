"""The toy's floors: a row's real pixels are its tokens, so an image's
operations go with them, its attention with their square, and a call's
bytes with the experts its tokens can touch."""

import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent))

from net import DIM, EXPERTS, HIDDEN, PATCH, PER_TOKEN, VOCAB  # noqa: E402


def tokens(row: dict) -> float:
    return row["px_real"] / row["rows_real"] / (PATCH * PATCH)


def image_flops(model: dict, row: dict) -> float:
    t = tokens(row)
    per_token = PATCH * PATCH * 3 * DIM + 4 * DIM * DIM + DIM * EXPERTS + PER_TOKEN * 2 * DIM * HIDDEN
    return 2 * (t * per_token + t * t / 2 * 2 * DIM + model["answer_steps"] * DIM * VOCAB)


def serve_bytes(model: dict, row: dict) -> float:
    rows = row["rows_real"] / row["batches"]
    held = min(EXPERTS, PER_TOKEN * rows * tokens(row))   # experts that this many tokens can reach
    params = PATCH * PATCH * 3 * DIM + 4 * DIM * DIM + DIM * EXPERTS + held * 2 * DIM * HIDDEN + DIM * VOCAB
    return 2 * params + row["px_real"] / row["batches"] * 3 + rows * model["answer_steps"] * model["topk"] * 8
