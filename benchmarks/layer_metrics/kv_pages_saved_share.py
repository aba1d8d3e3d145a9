"""Serve engine: 100 x (1 - ``ds_serve_kv_page_steps_total`` /
``ds_serve_kv_page_steps_one_budget_total``), window begin to profiler
start: of the page x layer x iterations ONE page budget a layer would have
held for the same slots and positions, the share two budgets (a ring of
``sliding_window`` rows for the sliding layers, every position for the
global ones) did not hold.  None for a program without the counters (one
budget: it has nothing to save)."""

from benchmarks.lib.moe_counters import grown

HELD, ONE = "ds_serve_kv_page_steps_total", \
    "ds_serve_kv_page_steps_one_budget_total"


def read(ctx):
    g = grown(ctx, HELD, ONE)
    return 100.0 * (1.0 - g[HELD] / g[ONE]) if g and g[ONE] else None
