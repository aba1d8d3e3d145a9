"""Model: traced time of ``selective_scan_chunk`` (one call a mamba1 layer a
chunk program: the chunk's rows walked in order, a channel tile's state in
registers) over the busy time of the chunk programs (``jit_prefill``), chip
0: how much of a prefill chunk the recurrence is, beside the matrix products
that stream the layer's weights.  None for a program without the kernel."""

from benchmarks.lib.kda_costs import decode_share


def read(ctx):
    return decode_share(ctx, "selective_scan_chunk", "jit_prefill")
