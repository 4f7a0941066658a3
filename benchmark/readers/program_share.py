"""Share of the device's busy time spent in the programs whose name matches."""

from benchmark.readers._stats import program_time


def read(ctx, match):
    seconds, calls = program_time(ctx, match)
    if not calls or not ctx.trace["busy_s"]:
        return None
    return 100.0 * seconds / ctx.trace["busy_s"]
