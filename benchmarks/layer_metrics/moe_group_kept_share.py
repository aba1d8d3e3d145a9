"""Serve engine: ``ds_serve_moe_group_kept_total`` over the (live row, expert
layer, decode step) triples (``ds_serve_moe_assignments_total`` /
``num_experts_per_tok``), window begin to profiler start, in percent: of the
rows the decode blocks routed, the share whose kept groups of experts
include a group with an expert THIS chip holds.  Says that the router's
group limit is at work in the served path (about 50 where 4 of 8 groups are
kept and the chip's experts lie in one; 100 = the limit is off) and why the
expert block sees the rows it sees.  None for a program without the counter
and for a model whose router has one group (the counter does not move)."""

from benchmarks.lib.moe_counters import grown

KEPT, ALL = "ds_serve_moe_group_kept_total", "ds_serve_moe_assignments_total"


def read(ctx):
    mc = ctx["config"]["model_config"]
    g = grown(ctx, KEPT, ALL)
    if not g or not g[ALL] or mc.get("moe_n_group", 1) < 2:
        return None
    return 100.0 * g[KEPT] * mc["num_experts_per_tok"] / g[ALL]
