"""What the program recorded about the traced stretch (``/stats -> profile``,
read after the window: the recording's ``[t_start, t_stop]`` on the monotonic
clock and the batch records that met it), held against the device trace.

- ``idle_starved``: % of the stretch in which no batch stood between its
  ``t_launch`` and its ``t_done``: the device had nothing launched at all.
- ``idle_inflight``: the device's idle share of the trace less
  ``idle_starved``, floored at 0: idle while a batch was launched (the H2D
  copy and its re-layout, dispatch, the D2H wait).
- ``rows_mean``: mean real rows of the batches launched inside the stretch,
  to hold against the window's ``batch_mean_rows``.

None where the server kept no profile block (an older server)."""

from benchmark.readers._stats import dig


def starved_share(profile: dict) -> float | None:
    t0, t1 = profile["t_start"], profile["t_stop"]
    if t1 <= t0:
        return None
    flights = sorted((max(b["t_launch"], t0), min(b["t_done"] if b["t_done"] is not None else t1, t1))
                     for b in profile["batches"] if b.get("t_launch") is not None)
    covered, end = 0.0, t0
    for a, z in flights:
        if z > end:
            covered += z - max(a, end)
            end = z
    return 100.0 * (1.0 - covered / (t1 - t0))


def read(ctx, what):
    profile = dig(ctx.after, "profile")
    if not profile:
        return None
    if what == "idle_starved":
        return starved_share(profile)
    if what == "idle_inflight":
        starved = starved_share(profile)
        if starved is None or not ctx.trace:
            return None
        idle = 100.0 * (1.0 - ctx.trace["busy_s"] / ctx.trace["window_s"])
        return max(0.0, idle - starved)
    if what == "rows_mean":
        rows = [b["rows"] for b in profile["batches"]
                if b.get("t_launch") is not None and profile["t_start"] <= b["t_launch"] <= profile["t_stop"]]
        return sum(rows) / len(rows) if rows else None
    raise ValueError(f"profile reader: unknown {what!r}")
