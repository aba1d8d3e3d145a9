"""Model: ``ds_serve_decode_tokens_total`` over
``ds_serve_dsa_rows_gathered_total``, window begin to profiler start: of the
(row, step) pairs whose selected rows an indexed layer's decode steps looked
up, gathered and attended (the live rows in groups, the rows that pad a
step's last group included), the share in which the row decoded: how full
the groups are.  A program that works every slot would read
``decode_rows_live_share``; None for a program without the second counter
(a parent whose selection works all ``num_slots`` rows a step)."""

from benchmarks.lib.moe_counters import grown

LIVE, GATHERED = "ds_serve_decode_tokens_total", \
    "ds_serve_dsa_rows_gathered_total"


def read(ctx):
    g = grown(ctx, LIVE, GATHERED)
    return 100.0 * g[LIVE] / g[GATHERED] if g and g[GATHERED] else None
