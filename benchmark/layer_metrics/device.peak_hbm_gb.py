"""device.peak_hbm_gb: Peak device memory on the fullest chip (memory_stats), in GB of 1e9 bytes."""


def read(ctx):
    peak = ctx["memory_peak_bytes"]
    return peak / 1e9 if ctx["job"] == "stream" and peak else None
