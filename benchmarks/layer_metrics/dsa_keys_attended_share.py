"""Serve engine: ``ds_serve_dsa_keys_attended_total`` over
``ds_serve_dsa_keys_scored_total``, window begin to profiler start: of the
keys in the live decode rows' contexts (each scored by the indexer), the
share the full layers then attend (``min(pos + 1, 2048)`` of ``pos + 1`` a
row a step): what the selection cuts at this mix.  None for a program
without the counters."""

from benchmarks.lib.dsa_costs import ATTENDED, SCORED
from benchmarks.lib.moe_counters import grown


def read(ctx):
    g = grown(ctx, ATTENDED, SCORED)
    return 100.0 * g[ATTENDED] / g[SCORED] if g and g[SCORED] else None
