"""Kernels: share of its roofline the Mamba-1 state update reached.  Least
time for the traced calls of ``mamba1_decode_step``: the LIVE rows' steps
(the loop's own marks, ``kda_costs.traced_steps``) x the mamba1 layers x
``mamba1_costs.state_step_bytes`` (the state read and written once, u, dt,
y, B, C; float32) over the chip's bytes/s, against the kernel's traced time.
The kernel also forms one exponential a state element, on a unit the chip
publishes no peak for: a reading under the bytes' bound is that, or parked
rows visited.  None for a program without the kernel and for a configuration
without mamba1 layers."""

from benchmarks.lib.kda_costs import byte_roofline_share, traced_steps
from benchmarks.lib.mamba1_costs import mamba1_layers, state_step_bytes


def read(ctx):
    mc = ctx["config"]["model_config"]
    if not ctx["trace_window"] or not mamba1_layers(mc):
        return None
    steps, _ = traced_steps(ctx)
    return byte_roofline_share(ctx, "mamba1_decode_step", mamba1_layers(mc),
                               steps * state_step_bytes(mc))
