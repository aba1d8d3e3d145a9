"""Serve engine: ``ds_serve_mla_rows_expanded_total`` over
``ds_serve_mla_rows_written_total``, window begin to profiler start: how
many (latent row, latent layer) pairs the prefill chunk programs DECOMPRESSED
to per-head keys and values for each pair they wrote.  A chunk attends every
earlier row of its request and expands it again, a key block at a time, so
this is how many times prefill pays ``W_kvb`` for one cached row: about half
the chunks of a prompt (1 for one-chunk prompts; lower is better, 1 what a
chunk attention that keeps the rows compressed would read).  None for a
program without the counters."""

from benchmarks.lib.moe_counters import grown

EXPANDED, WRITTEN = "ds_serve_mla_rows_expanded_total", \
    "ds_serve_mla_rows_written_total"


def read(ctx):
    g = grown(ctx, EXPANDED, WRITTEN)
    return g[EXPANDED] / g[WRITTEN] if g and g[WRITTEN] else None
