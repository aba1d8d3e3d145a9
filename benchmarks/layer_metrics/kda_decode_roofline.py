"""Kernels: share of its roofline the recurrent-state update reached.  Least
time for the traced calls of ``kda_decode_step``: the LIVE rows' state bytes,
``kda_costs.state_step_bytes`` a row a linear layer a step (the head states
read and written once; the kernel is byte-bound, half a FLOP a byte), over
the chip's bytes/s, against the kernel's traced time.  The live rows' steps
come from the loop's own marks (``kda_costs.traced_steps``).  A kernel that
visits parked rows moves their states too and reads low here, which is the
truth.  None for a program without the kernel and for a configuration
without linear-attention layers."""

from benchmarks.lib.kda_costs import (byte_roofline_share, kind_counts,
                                      state_step_bytes, traced_steps)


def read(ctx):
    mc = ctx["config"]["model_config"]
    if not ctx["trace_window"] or not kind_counts(mc)[0]:
        return None
    steps, _ = traced_steps(ctx)
    return byte_roofline_share(ctx, "kda_decode_step", kind_counts(mc)[0],
                               steps * state_step_bytes(mc))
