"""The device's idle share of the traced window, and its peak memory: over
the process's life, and as warm-up alone left it (``/stats`` right after
boot), so that a window whose own batches raise the peak shows."""

from benchmark.readers._stats import dig


def read(ctx, what):
    if what == "idle_share":
        return 100.0 * (1.0 - ctx.trace["busy_s"] / ctx.trace["window_s"])
    if what in ("peak_hbm_gb", "peak_hbm_boot_gb"):
        stats = ctx.after if what == "peak_hbm_gb" else ctx.stats_boot
        peaks = [d.get("peak_bytes_in_use") for d in dig(stats, "device_memory") or []]
        peaks = [p for p in peaks if p]
        return max(peaks) / 1e9 if peaks else None
    raise ValueError(f"device reader: unknown {what!r}")
