"""Deltas of ``/stats`` counters over the measured window, shared by readers."""

from __future__ import annotations


def dig(doc: dict, path: str):
    for part in path.split("."):
        if not isinstance(doc, dict) or part not in doc:
            return None
        doc = doc[part]
    return doc


def delta(ctx, path: str) -> float | None:
    a, b = dig(ctx.before, path), dig(ctx.after, path)
    return None if a is None or b is None else b - a


def buckets(ctx) -> list[dict]:
    """Per (canvas, batch bucket) of the window: batches, real rows, rows
    dispatched and real pixels, from ``/stats -> batcher.builders.padding``."""
    after = dig(ctx.after, "batcher.builders.padding") or {}
    before = dig(ctx.before, "batcher.builders.padding") or {}
    out = []
    for key, a in after.items():
        b = before.get(key, {})
        row = {k: a[k] - b.get(k, 0) for k in ("batches", "rows_real", "rows_dispatched", "px_real")}
        if row["batches"] > 0:
            out.append({"canvas": a["canvas"], "batch_bucket": a["batch_bucket"], **row})
    return out


def program_time(ctx, match: str) -> tuple[float, int]:
    """(seconds, calls) of the traced programs whose name contains ``match``."""
    rows = [r for r in ctx.trace["programs"] if match in r[0]]
    return sum(r[1] for r in rows), sum(r[2] for r in rows)
