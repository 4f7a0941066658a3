"""Device milliseconds in the operations whose name contains ``match``, per
call of the programs whose name contains ``program``, or per real image
those calls served (``per``: ``"call"`` or ``"image"``).

An operation's name is what ``xplane.py::short_op`` keeps of its HLO line:
the op's own name and the largest array it yields, so a kernel is found by
the name it was given (``unpack_planes.1 u8[32,3,4096,4096]``) inside
whatever program runs it. The images are the traced calls times the window's
mean real rows a batch, as ``step_mfu`` counts them. A ``while`` holds its
body's operations, each an event of its own: match the loop or its body,
not both. None where no such operation or no such program ran."""

from benchmark.readers._stats import buckets, program_time


def read(ctx, match, program, per="call"):
    seconds = [r[1] for r in ctx.trace["ops"] if match in r[0]]
    _, calls = program_time(ctx, program)
    if not seconds or not calls:
        return None
    if per == "call":
        return 1e3 * sum(seconds) / calls
    if per == "image":
        rows = buckets(ctx)
        batches, real = sum(r["batches"] for r in rows), sum(r["rows_real"] for r in rows)
        return 1e3 * sum(seconds) / (calls * real / batches) if real else None
    raise ValueError(f"op_time reader: per {per!r} is neither 'call' nor 'image'")
