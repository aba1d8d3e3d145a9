"""Serve engine: rows on the fullest expert over rows on the average
expert, over the layers and decode steps of the window
(``ds_serve_moe_max_load_total`` x experts /
``ds_serve_moe_assignments_total``, window begin to profiler start): 1 is a
perfectly even router.  What a kernel that walks only the routed rows would
be bound by."""

from benchmarks.lib.moe_counters import grown

MAX, ALL = "ds_serve_moe_max_load_total", "ds_serve_moe_assignments_total"


def read(ctx):
    g = grown(ctx, MAX, ALL)
    experts = ctx["config"]["model_config"].get("num_experts")
    if not g or not g[ALL] or not experts:
        return None
    return g[MAX] * experts / g[ALL]
