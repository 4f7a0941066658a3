"""Device milliseconds per call of the programs whose name matches."""

from benchmark.readers._stats import program_time


def read(ctx, match):
    seconds, calls = program_time(ctx, match)
    return 1e3 * seconds / calls if calls else None
