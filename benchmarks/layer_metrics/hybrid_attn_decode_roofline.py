"""Kernels: share of its roofline the decode attention kernel reached in a
model whose pages lie in the ``full_attention`` layers only (linear-attention
layers beside them keep a state a slot): ``flash_decode_paged_roofline``'s own
reading with the layers that really call the kernel
(``hybrid_costs.full_layers``: one call a full layer a step, over that
layer's pages) in the place of ``num_layers``.  That reader takes ONE call's
bytes (K and V of every context token of every LIVE row, from the loop's
records of the traced iterations) times ``num_layers``, over the kernel's
traced seconds: for one full layer in four, four times the truth, which is
why this cell is not on its list.  None where it reads None (no kernel, no
trace, no live row) and for a configuration without such layers."""

from benchmarks.lib.hybrid_costs import full_layers


def read(ctx):
    mc = ctx["config"]["model_config"]
    if not full_layers(mc):
        return None
    counted = dict(ctx["config"],
                   model_config=dict(mc, num_layers=full_layers(mc)))
    return ctx["bench"].reader("flash_decode_paged_roofline").read(
        dict(ctx, config=counted))
