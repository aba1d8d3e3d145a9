"""Kernels: share of its roofline the decode attention kernel reached in a
looped stack: ``flash_decode_paged_roofline``'s own reading with the cache
layers a step really reads (``loop_costs.cache_layers``: passes x layers
calls a step, each over a cache layer of its own) in the place of
``num_layers``.  That reader takes ONE call's bytes (K and V of every
context token of every LIVE row, from the loop's records of the traced
iterations) times ``num_layers``, over the kernel's traced seconds: for a
stack run four times a quarter of the truth, which is why this cell is not
on its list.  None where it reads None (no kernel, no trace, no live row)."""

from benchmarks.lib.loop_costs import cache_layers


def read(ctx):
    mc = ctx["config"]["model_config"]
    looped = dict(ctx["config"],
                  model_config=dict(mc, num_layers=cache_layers(mc)))
    return ctx["bench"].reader("flash_decode_paged_roofline").read(
        dict(ctx, config=looped))
