"""Model: traced calls of the decode attention kernel (``flash_decode_paged``)
over (executions of the decode-block program ``jit_body`` x
``decode_block_tokens`` x ``num_layers``), chip 0: how many times a decode
step ran its layer stack, by the device's own count.  A looped stack of
``total_ut_steps`` passes reads that number whatever the program says of
itself, LESS what the traced window's end cuts: programs and kernel calls are
both counted by their start, the profiler's start leaves the chip drained, so
the last block is counted whole and its calls past the end are not: about
half a block in the ten of a traced second (Ouro's 4 passes read 3.8, where
3 would read 2.85 and 5 4.75).  None for a program without the kernel."""

KERNEL, PROGRAM = "flash_decode_paged", "jit_body"


def read(ctx):
    tr = ctx["trace"]
    if tr is None or KERNEL not in tr["kernels"] \
            or not tr["programs"].get(PROGRAM, {}).get("count"):
        return None
    steps = tr["programs"][PROGRAM]["count"] \
        * int(ctx["cell"]["engine"]["decode_block_tokens"])
    return tr["kernels"][KERNEL]["count"] \
        / (steps * ctx["config"]["model_config"]["num_layers"])
