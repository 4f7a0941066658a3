"""Share of the measured window that a ``/stats`` clock counted: the clock's
delta between the two reads over the window ``images_per_s`` divides by,
from the generator's start to ``--seconds`` or the last answer
(``Outcome.done``), whichever is later, as ``starved_share.py`` takes it.
For a clock that runs only while a batch is launched (the h2d-bound clock),
nothing accrues outside the window, so nothing is taken off. None where the
path is missing (a server from before the clock)."""

from benchmark.readers._stats import delta


def read(ctx, path):
    counted = delta(ctx, path)
    if counted is None or not ctx.outcomes:
        return None
    window = max([ctx.seconds, *(o.done for o in ctx.outcomes)])
    return 100.0 * counted / window
