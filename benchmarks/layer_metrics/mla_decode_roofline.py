"""Kernels: share of its roofline latent decode attention reached.  Least
time for the traced calls of ``mla_decode_paged``: the rows the live queries
ATTEND, ``kda_costs.latent_row_bytes`` each (576 values stored once for all
heads: the lane padding is not counted), in every latent layer, over the
chip's bytes/s.  Byte-bound: 32 heads against one shared row are 2 x 32 x
(576 + 512) FLOPs for 1,152 bytes, 60 FLOPs a byte under the v5e's ridge of
240.  The rows come from the loop's own marks (``kda_costs.traced_steps``).
None for a program without the kernel and for a configuration without
latent-attention layers."""

from benchmarks.lib.kda_costs import (byte_roofline_share, kind_counts,
                                      latent_row_bytes, traced_steps)


def read(ctx):
    mc = ctx["config"]["model_config"]
    if not ctx["trace_window"] or not kind_counts(mc)[1]:
        return None
    _, rows = traced_steps(ctx)
    return byte_roofline_share(ctx, "mla_decode_paged", kind_counts(mc)[1],
                               rows * latent_row_bytes(mc))
