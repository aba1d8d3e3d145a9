"""Model: traced time of ``mla_decode_paged`` (one call a latent-attention
layer a decode step: the live rows' latent pages, each fetched once and used
as keys and as values) over the busy time of the decode-block programs
(``jit_body``), chip 0: how much of a decode step latent attention is.  None
for a program without the kernel."""

from benchmarks.lib.kda_costs import decode_share


def read(ctx):
    return decode_share(ctx, "mla_decode_paged")
