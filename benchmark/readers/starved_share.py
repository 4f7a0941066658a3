"""Share of the measured window in which no batch stood between its launch
and its outputs on any replica: ``/stats -> batcher.lifecycle``'s starved
clock over the window.

The two ``/stats`` reads lie further apart than the window: the first is
taken before the senders start, the second after every answer is in and,
in a traced run, after the profiler has written its file (minutes, on a
trace of millions of device events). The premise is that nothing is
launched outside the window, so all of that time is starved; it is taken
off both the starved seconds and the elapsed seconds. The window is the one
``images_per_s`` divides by: from the generator's start to ``--seconds`` or
the last answer (``Outcome.done``), whichever is later. Nothing is clamped:
where the premise fails (a batch in flight outside the window), the share
falls below 0 and shows it."""

from benchmark.readers._stats import delta

LIFE = "batcher.lifecycle."


def read(ctx):
    starved, between = delta(ctx, LIFE + "starved_s_total"), delta(ctx, LIFE + "now_s")
    if starved is None or between is None or not ctx.outcomes:
        return None
    window = max([ctx.seconds, *(o.done for o in ctx.outcomes)])
    return 100.0 * (starved - (between - window)) / window
