"""Model: traced time of ``kda_decode_step`` (one call a linear-attention
layer a decode step: the live rows' recurrent states read, updated and
written back) over the busy time of the decode-block programs
(``jit_body``), chip 0: how much of a decode step the state update is.  With
``mla_decode_share`` and ``moe_decode_share`` it says what the three
mechanisms of a hybrid linear-attention mixture-of-experts model take of a
step.  None for a program without the kernel."""

from benchmarks.lib.kda_costs import decode_share


def read(ctx):
    return decode_share(ctx, "kda_decode_step")
