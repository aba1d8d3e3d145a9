"""Kernels: ``ds_serve_decode_tokens_total`` over
``ds_serve_decode_row_slots_total``, window begin to profiler start: of the
(row, step) pairs of the decode blocks' batch (``num_slots`` x
``decode_block_tokens`` a block), the share in which the row decoded.  The
decode attention kernels' grid visits those rows only, so 100 less it is the
share of row visits they skip; it describes the traffic, nothing optimises
it.  None for a program without the second counter (a parent whose kernels
visit every slot)."""

from benchmarks.lib.moe_counters import grown

LIVE, SLOTS = "ds_serve_decode_tokens_total", \
    "ds_serve_decode_row_slots_total"


def read(ctx):
    g = grown(ctx, LIVE, SLOTS)
    return 100.0 * g[LIVE] / g[SLOTS] if g and g[SLOTS] else None
