"""Set-up's parts: the boot of the child that is measured, the boot before it
that compiled (0 where the executables were in the cache: ``run.py::boot``),
the executables that set-up had to compile, and the executables compiled
while the window ran (which must be none)."""

from benchmark.readers._stats import delta, dig


def read(ctx, what):
    if what == "boot_s":
        return ctx.boot_s
    if what == "boot_compile_s":
        return ctx.compile_boot_s
    if what == "boot_aot_misses":
        return dig(ctx.stats_compile_boot or ctx.stats_boot, "aot_cache.misses_total")
    if what == "compiles_in_window":
        return delta(ctx, "aot_cache.misses_total")
    raise ValueError(f"boot reader: unknown {what!r}")
