"""Kernels: ``ds_serve_attn_keys_attended_total`` over
``ds_serve_attn_keys_fetched_total``, window begin to profiler start: of the
keys the decode attention kernel over per-head K/V pages brings into VMEM
for its live rows (a row's pages before its last whole, the last in pieces
of ``FETCH_ROWS`` tokens up to ``pos``: the kernel's own rule, counted on the
host from the positions), the share the rows attend (``pos + 1`` a row a
step).  What is missing from 100 is bytes the kernel moves for nothing; a
kernel that fetched every page whole would read 76-87 at these contexts.
None for a program without the counters (a parent that fetches whole pages
and counts neither)."""

from benchmarks.lib.moe_counters import grown

ATTENDED, FETCHED = "ds_serve_attn_keys_attended_total", \
    "ds_serve_attn_keys_fetched_total"


def read(ctx):
    g = grown(ctx, ATTENDED, FETCHED)
    return 100.0 * g[ATTENDED] / g[FETCHED] if g and g[FETCHED] else None
