"""A ratio of ``/stats`` counters over the window: the delta of ``num`` (a
dotted path, or a list of paths whose deltas add) over the delta of ``den``,
times ``scale``; without ``den``, the delta itself. None where a path is
missing (a server from before the counter) or ``den`` did not move.

The lifecycle metrics read ``batcher.lifecycle`` this way (seconds per batch,
bytes per batch, batches sealed for a reason over all batches, starved
seconds over the seconds between the two reads); a count in the window is a
ratio with no ``den``."""

from benchmark.readers._stats import delta


def read(ctx, num, den=None, scale=1.0):
    parts = [delta(ctx, path) for path in ([num] if isinstance(num, str) else num)]
    if any(p is None for p in parts):
        return None
    if den is None:
        return scale * sum(parts)
    over = delta(ctx, den)
    return scale * sum(parts) / over if over else None
