"""Serve engine: ``ds_serve_moe_local_assignments_total`` over
``ds_serve_moe_assignments_total``, window begin to profiler start: of the
(row, expert) choices the live decode rows made, the share that chose an
expert THIS chip holds (12.5 under uniform routing over eight ranks; 100
where the chip holds every expert).  What the chip's share of the experts
leaves of the expert block's rows.  None for a program without the
counter."""

from benchmarks.lib.moe_counters import grown

LOCAL, ALL = "ds_serve_moe_local_assignments_total", \
    "ds_serve_moe_assignments_total"


def read(ctx):
    g = grown(ctx, LOCAL, ALL)
    return 100.0 * g[LOCAL] / g[ALL] if g and g[ALL] else None
