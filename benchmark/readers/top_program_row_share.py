"""Share of the window's real rows that went through the largest program
the server holds: its top canvas at its top batch bucket (``/stats ->
config``). That program's working set is the process's peak of device
memory, so this says how much of the window's work the peak stands for."""

from benchmark.readers._stats import buckets, dig


def read(ctx):
    canvases, batch = dig(ctx.after, "config.canvas_buckets"), dig(ctx.after, "config.batch_buckets")
    rows = buckets(ctx)
    real = sum(r["rows_real"] for r in rows)
    if not canvases or not batch or not real:
        return None
    top = sum(r["rows_real"] for r in rows
              if r["canvas"] == max(canvases) and r["batch_bucket"] == max(batch))
    return 100.0 * top / real
