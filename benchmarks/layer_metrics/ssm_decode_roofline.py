"""Kernels: share of its roofline the state-space update reached.  Least time
for the traced calls of ``ssm_decode_step``: the LIVE rows' state bytes,
``ssm_costs.state_step_bytes`` a row a mamba2 layer a step (the head states
read and written once; the kernel is byte-bound, half a FLOP a byte), over
the chip's bytes/s, against the kernel's traced time.  The live rows' steps
come from the loop's own marks (``kda_costs.traced_steps``).  A kernel that
visits parked rows moves their states too and reads low here, which is the
truth.  None for a program without the kernel and for a configuration
without mamba2 layers."""

from benchmarks.lib.kda_costs import byte_roofline_share, traced_steps
from benchmarks.lib.ssm_costs import mamba_layers, state_step_bytes


def read(ctx):
    mc = ctx["config"]["model_config"]
    if not ctx["trace_window"] or not mamba_layers(mc):
        return None
    steps, _ = traced_steps(ctx)
    return byte_roofline_share(ctx, "ssm_decode_step", mamba_layers(mc),
                               steps * state_step_bytes(mc))
