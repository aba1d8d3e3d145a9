"""Model: traced time of ``fused_moe_mlp`` over the busy time of the
decode-block programs (``jit_body``), chip 0: how much of a decode step is
the expert block."""

KERNEL, PROGRAM = "fused_moe_mlp", "jit_body"


def read(ctx):
    tr = ctx["trace"]
    if tr is None or KERNEL not in tr["kernels"] \
            or not tr["programs"].get(PROGRAM, {}).get("busy_s"):
        return None
    return 100.0 * tr["kernels"][KERNEL]["seconds"] \
        / tr["programs"][PROGRAM]["busy_s"]
