"""Serve engine: ``ds_serve_state_row_steps_total`` over
``ds_serve_state_row_steps_visited_total``, window begin to profiler start:
of the (row, linear layer, decode step) triples whose recurrent state the
decode kernel read and wrote, the share in which the row decoded.  100 where
the kernel's grid follows the live rows; ``decode_rows_live_share`` where it
visits every slot.  None for a program without the counters."""

from benchmarks.lib.moe_counters import grown

LIVE, VISITED = "ds_serve_state_row_steps_total", \
    "ds_serve_state_row_steps_visited_total"


def read(ctx):
    g = grown(ctx, LIVE, VISITED)
    return 100.0 * g[LIVE] / g[VISITED] if g and g[VISITED] else None
