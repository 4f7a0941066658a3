"""Host milliseconds per request or per image in the named span stages
(``/stats -> tracing.stages`` totals, as deltas over the window)."""

from benchmark.readers._stats import delta


def read(ctx, stages, per="request"):
    total = 0.0
    for stage in stages:
        d = delta(ctx, f"tracing.stages.{stage}.total_ms")
        if d is None:
            return None
        total += d
    ok = [o for o in ctx.outcomes if o.answers is not None]
    n = len(ok) if per == "request" else sum(o.images for o in ok)
    return total / n if n else None
