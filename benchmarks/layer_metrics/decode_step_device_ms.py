"""Model: device busy time inside executions of the decode-block program
(``jit_body``), over decode steps executed (blocks x
``decode_block_tokens``), chip 0."""

PROGRAM = "jit_body"


def read(ctx):
    tr = ctx["trace"]
    if tr is None or PROGRAM not in tr["programs"]:
        return None
    rec = tr["programs"][PROGRAM]
    k = int(ctx["cell"]["engine"]["decode_block_tokens"])
    return rec["busy_s"] * 1e3 / (rec["count"] * k)
