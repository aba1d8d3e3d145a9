"""Model: traced time of ``mamba1_decode_step`` (one call a mamba1 layer a
decode step: the live rows' states read, decayed by ``exp(dt A)`` formed in
the kernel, updated and written back) over the busy time of the decode-block
programs (``jit_body``), chip 0: how much of a decode step the Mamba-1 state
update is.  Nemotron's ``ssm_decode_share`` reads the Mamba-2 kernel
(``ssm_decode_step``: a row of decays made outside it).  None for a program
without the kernel."""

from benchmarks.lib.kda_costs import decode_share


def read(ctx):
    return decode_share(ctx, "mamba1_decode_step")
