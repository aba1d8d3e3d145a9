"""Kernels: of the traced time of the grouped matmuls
(``moe_grouped_prefill_share``'s numerator: the Pallas kernel
``moe_grouped_matmul`` plus every ``ragged-dot*`` instruction), the share
the kernel ran, chip 0: 0 for a program on ``jax.lax.ragged_dot`` (every
program before PR 64), 100 where the kernel serves every call, between the
two where a shape rule keeps some calls on XLA's.  None without a trace or
where the window holds no grouped matmul."""


def read(ctx):
    tr = ctx["trace"]
    if tr is None:
        return None
    kernel, xla = ctx["bench"].reader("moe_grouped_prefill_share").seconds(tr)
    if not kernel + xla:
        return None
    return 100.0 * kernel / (kernel + xla)
