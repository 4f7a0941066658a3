"""A kernel's share of its roofline: the least seconds its calls in the
window's mean serve call could take, over the seconds the trace gives them.

The kernel is found by the name it was given (``match``, in
``ctx.trace["ops"]``: ``mla_prefill.3 bf16[16,64,1024,128]``), inside the
programs whose name contains ``program``; its operations and bytes a call
come from the configuration's floors module (``kernel_floor(model, row,
kernel)``, per row of the window's padding table), the larger of the two
over the chip's peaks is the floor, and which binds is the kernel's to say.
As the other shares, the traced stretch is taken to hold the window's mix
of batches. None where the program ran no such kernel, the trace holds no
operations, or the floors module knows no such kernel: a program from
before the kernel leaves the metric out."""

from benchmark import cost
from benchmark.peaks import device_peak
from benchmark.readers._stats import buckets, program_time


def read(ctx, match, program):
    seconds = sum(r[1] for r in ctx.trace.get("ops", ()) if match in r[0])
    _, calls = program_time(ctx, program)
    rows = buckets(ctx)
    batches = sum(r["batches"] for r in rows)
    kernel_floor = getattr(cost.load_floors(ctx.config), "kernel_floor", None)
    if not seconds or not calls or not batches or kernel_floor is None:
        return None
    peak_flops, peak_bytes = device_peak(ctx.device["kind"])
    floor = 0.0
    for r in rows:
        work = kernel_floor(ctx.config["model"], r, match)
        if work is None:
            return None
        floor += r["batches"] * max(work[0] / peak_flops, work[1] / peak_bytes)
    return 100.0 * (floor / batches) / (seconds / calls)
