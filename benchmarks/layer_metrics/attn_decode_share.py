"""Model: traced time of the decode attention kernel (``flash_decode_paged``,
one call a layer a step, over ring pages or full pages) over the busy time
of the decode-block programs (``jit_body``), chip 0: how much of a decode
step is attention.  With ``moe_decode_share`` it says how much of a step the
two mechanisms of a windowed mixture-of-experts model are.  None for a
program without the kernel."""

KERNEL, PROGRAM = "flash_decode_paged", "jit_body"


def read(ctx):
    tr = ctx["trace"]
    if tr is None or KERNEL not in tr["kernels"] \
            or not tr["programs"].get(PROGRAM, {}).get("busy_s"):
        return None
    return 100.0 * tr["kernels"][KERNEL]["seconds"] \
        / tr["programs"][PROGRAM]["busy_s"]
