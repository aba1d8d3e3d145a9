"""Device: ``memory_stats()["peak_bytes_in_use"]`` after the window, the
largest over the chips, in GB (1e9 bytes)."""


def read(ctx):
    peak = ctx["device"].get("memory_peak_bytes")
    return peak / 1e9 if peak else None
