"""Real rows per dispatched batch over the window."""

from benchmark.readers._stats import buckets


def read(ctx):
    rows = buckets(ctx)
    batches = sum(r["batches"] for r in rows)
    return sum(r["rows_real"] for r in rows) / batches if batches else None
