"""Device: model FLOP/s utilisation: tokens per second times the forward
and backward FLOPs a token needs (``costs.train_flops_per_token``,
recomputation not counted) over chips times the chip's bf16 peak.  An
end-to-end utilisation, named as one; not a roofline share."""

from benchmarks.lib.costs import train_flops_per_token


def read(ctx):
    if ctx["peaks"] is None:
        return None
    loop = ctx["loop"]
    flops = train_flops_per_token(ctx["config"]["model_config"], loop["seq"])
    return 100.0 * ctx["end_to_end"]["tokens_per_s"] * flops / (
        loop["chips"] * ctx["peaks"]["bf16_flops_per_s"])
