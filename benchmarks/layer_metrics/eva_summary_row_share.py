"""Kernels: ``ds_serve_eva_summary_rows_total`` over it plus
``ds_serve_eva_window_rows_total``, window begin to profiler start: of the
rows the live decode queries attended, the share that were chunk summaries
of closed windows and not exact window rows.  What a request's context
beyond its window costs under EVA.  None for a program without the
counters."""

from benchmarks.lib.moe_counters import grown

SUMMARY, WINDOW = "ds_serve_eva_summary_rows_total", \
    "ds_serve_eva_window_rows_total"


def read(ctx):
    g = grown(ctx, SUMMARY, WINDOW)
    total = g[SUMMARY] + g[WINDOW] if g else 0
    return 100.0 * g[SUMMARY] / total if total else None
