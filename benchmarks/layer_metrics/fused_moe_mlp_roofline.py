"""Kernels: share of its roofline the fused expert block reached.  Least
time for the traced calls of ``fused_moe_mlp``: each call (one layer of one
decode step) must read the three matrices of all of the layer's experts
once, ``moe_costs.expert_weight_bytes`` over the chip's bytes/s.  The kernel
is byte-bound at decode batch sizes: 64 rows against every expert are 51
GFLOP a call, 0.26 ms at the chip's peak, against 0.98 ms for 805 MB."""

from benchmarks.lib.costs import least_seconds
from benchmarks.lib.moe_costs import expert_weight_bytes

KERNEL = "fused_moe_mlp"


def read(ctx):
    tr = ctx["trace"]
    mc = ctx["config"]["model_config"]
    if tr is None or KERNEL not in tr["kernels"] or not mc.get("num_experts"):
        return None
    rec = tr["kernels"][KERNEL]
    least, _ = least_seconds(0.0, expert_weight_bytes(mc) * rec["count"],
                             ctx["peaks"])
    return 100.0 * least / rec["seconds"]
