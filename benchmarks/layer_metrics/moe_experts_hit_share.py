"""Serve engine: ``ds_serve_moe_expert_hits_total`` over
``ds_serve_moe_expert_slots_total``, window begin to profiler start: of the
(layer, decode step, expert) triples the decode blocks ran, the share in
which at least one live row had chosen the expert.  100 less it is what
skipping unhit experts could save of the expert weights' bytes."""

from benchmarks.lib.moe_counters import grown

HITS, SLOTS = "ds_serve_moe_expert_hits_total", \
    "ds_serve_moe_expert_slots_total"


def read(ctx):
    g = grown(ctx, HITS, SLOTS)
    return 100.0 * g[HITS] / g[SLOTS] if g and g[SLOTS] else None
