"""Roofline shares and the whole step's share of the chip's peak.

The trace gives each program's device time and calls; ``/stats`` gives the
window's batches by (canvas, batch bucket) with their real rows and pixels.
The traced stretch is taken to hold the window's mix of batches: the floor
per call is the window's mean, the time per call is the trace's.

Operations and bytes of a serve call are the architecture's: they come
from the floors module the configuration names (``cost.load_floors``), per
row of that table, so a kernel's share reads the same work whatever
implements it.

- ``serve``: least seconds for the window's mean serve call (the larger of
  operations over peak FLOP/s and bytes over peak bytes/s, summed per batch)
  over the traced seconds per call. Compute binds at large batches,
  bandwidth (the parameters, once per batch) at small ones.
- ``unpack``: (tight decoded bytes read + canvas bytes written) over peak
  bytes/s, over the traced unpack seconds. Bandwidth binds.
- ``step_mfu``: the floor operations of the images dispatched over (traced
  unpack + serve seconds) x peak FLOP/s.
"""

from benchmark import cost
from benchmark.peaks import device_peak

from benchmark.readers._stats import buckets, program_time


def read(ctx, kind, serve_match="serve", unpack_match="unpack"):
    rows = buckets(ctx)
    batches = sum(r["batches"] for r in rows)
    if not batches:
        return None
    peak_flops, peak_bytes = device_peak(ctx.device["kind"])
    model, floors = ctx.config["model"], cost.load_floors(ctx.config)
    serve_s, serve_calls = program_time(ctx, serve_match)
    unpack_s, unpack_calls = program_time(ctx, unpack_match)
    if kind == "serve":
        if not serve_calls:
            return None
        floor = sum(r["batches"] * cost.serve_floor_s(floors, model, r, peak_flops, peak_bytes)[0] for r in rows)
        return 100.0 * (floor / batches) / (serve_s / serve_calls)
    if kind == "unpack":
        if not unpack_calls:
            return None
        floor = sum(cost.unpack_floor_s(3 * r["px_real"], r["rows_real"] * r["canvas"] ** 2 * 3, peak_bytes)
                    for r in rows)
        # unpack programs run once per batch that ships ragged rows
        return 100.0 * (floor / batches) / (unpack_s / serve_calls) if serve_calls else None
    if kind == "step_mfu":
        if not serve_calls:
            return None
        images = serve_calls * sum(r["rows_real"] for r in rows) / batches
        return 100.0 * images * cost.step_flops(floors, model, rows) / ((serve_s + unpack_s) * peak_flops)
    raise ValueError(f"roofline reader: unknown kind {kind!r}")
