"""Kernels: share of its roofline the decode attention kernel reached in a
model whose layers are ONE mixer each (``models/ssm_moe.py``): K and V rows
lie in the pages of the ``full_attention`` layers only, at this form's group
of query heads a key-value head (16 in Nemotron-3-Nano: 1 KB a token a
layer).  ``flash_decode_paged_roofline``'s own reading with the layers that
really call the kernel (``ssm_costs.attention_layers``: one call a
``full_attention`` layer a step, over that layer's pages) in the place of
``num_layers``, as ``hybrid_attn_decode_roofline`` does for a pattern with
linear-attention layers (its ``hybrid_costs.full_layers`` counts 0 for a
pattern without them).  None where that reader reads None (no kernel, no
trace, no live row) and for a configuration without mamba2 layers."""

from benchmarks.lib.ssm_costs import attention_layers


def read(ctx):
    mc = ctx["config"]["model_config"]
    if not attention_layers(mc):
        return None
    counted = dict(ctx["config"],
                   model_config=dict(mc, num_layers=attention_layers(mc)))
    return ctx["bench"].reader("flash_decode_paged_roofline").read(
        dict(ctx, config=counted))
