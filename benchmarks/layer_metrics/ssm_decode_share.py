"""Model: traced time of ``ssm_decode_step`` (one call a mamba2 layer a decode
step: the live rows' states read, updated and written back) over the busy
time of the decode-block programs (``jit_body``), chip 0: how much of a
decode step the state-space update is.  With ``moe_decode_share`` and
``attn_decode_share`` it says what the three mixers of a hybrid state-space
mixture-of-experts model take of a step.  None for a program without the
kernel."""

from benchmarks.lib.kda_costs import decode_share


def read(ctx):
    return decode_share(ctx, "ssm_decode_step")
