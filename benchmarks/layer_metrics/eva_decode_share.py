"""Kernels: traced time of the EVA decode attention kernel plus the window
pooling kernel over the busy time of the decode-block programs
(``jit_body``), chip 0: how much of a decode step is the mechanism.  None
for a program without the kernel."""

KERNELS, PROGRAM = ("eva_decode_paged", "eva_summarize_paged"), "jit_body"


def read(ctx):
    tr = ctx["trace"]
    if tr is None or KERNELS[0] not in tr["kernels"] \
            or not tr["programs"].get(PROGRAM, {}).get("busy_s"):
        return None
    spent = sum(tr["kernels"][k]["seconds"] for k in KERNELS
                if k in tr["kernels"])
    return 100.0 * spent / tr["programs"][PROGRAM]["busy_s"]
