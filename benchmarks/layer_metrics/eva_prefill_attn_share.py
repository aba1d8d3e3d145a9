"""Kernels: traced time of the EVA chunk attention kernel
(``eva_chunk_attention``) over the busy time of the prefill chunk programs
(``jit_prefill``), chip 0: whether attention is still the larger part of a
chunk program.  None for a program without the kernel (a parent whose
chunks run the dense XLA form)."""

KERNEL, PROGRAM = "eva_chunk_attention", "jit_prefill"


def read(ctx):
    tr = ctx["trace"]
    if tr is None or KERNEL not in tr["kernels"] \
            or not tr["programs"].get(PROGRAM, {}).get("busy_s"):
        return None
    return 100.0 * tr["kernels"][KERNEL]["seconds"] \
        / tr["programs"][PROGRAM]["busy_s"]
