"""Kernels: traced time of the prefill chunk programs' grouped matmuls over
the busy time of those programs (``jit_prefill``), chip 0: the Pallas kernel
``moe_grouped_matmul`` (``ops/pallas/grouped_matmul.py``) PLUS every
``ragged-dot*`` instruction of the reduction's op table (XLA's grouped
matmul, ``jax.lax.ragged_dot``: what the chunk programs ran before PR 64 and
what a shape the kernel does not serve still runs), so one name reads a
program on either.  None without a trace, without a chunk program in the
window, or where the window holds neither (no expert layer)."""

KERNEL, XLA_OP, PROGRAM = "moe_grouped_matmul", "ragged-dot", "jit_prefill"


def seconds(tr):
    """(the kernel's, the ``ragged-dot*`` instructions') traced seconds."""
    kernel = tr["kernels"].get(KERNEL, {}).get("seconds", 0.0)
    xla = sum(v["seconds"] for name, v in tr.get("ops", {}).items()
              if name.startswith(XLA_OP))
    return kernel, xla


def read(ctx):
    tr = ctx["trace"]
    if tr is None or not tr["programs"].get(PROGRAM, {}).get("busy_s"):
        return None
    kernel, xla = seconds(tr)
    if not kernel + xla:
        return None
    return 100.0 * (kernel + xla) / tr["programs"][PROGRAM]["busy_s"]
