"""Serve engine: 90th percentile over requests of (first output token
visible) - (time the request was DUE), on the benchmark's clock; the tail
of the end-to-end ``ttft_p50_ms``.  Not an end-to-end metric itself: over
the ~40 requests a window leaves beyond it, its run-to-run spread (3.2%
on the v5e, PR 24) asks for a wider bound than a bound may be.  Left out
where fewer than ten requests lie beyond it.  Traced run: requests due at
least a second before the profiler started, so that the stall of its
start is in no sample."""

from benchmarks.lib.stats import highest_supported_percentile, percentile


def read(ctx):
    loop = ctx["loop"]
    horizon = loop["until_s"] - (1.0 if ctx["trace_window"] else 0.0)
    ttft = [(lv.t_first - a.due_s) * 1e3
            for lv, a in zip(loop["records"], loop["schedule"])
            if lv is not None and lv.t_first is not None
            and a.due_s < horizon]
    if (highest_supported_percentile(len(ttft)) or 0) < 90:
        return None
    return percentile(ttft, 90)
