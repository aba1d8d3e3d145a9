"""Kernels: device time in Pallas kernels (``tpu_custom_call``s, found by
their ``pallas_call(name=...)``) over device busy time, chip 0: how much
of a step is hand-written."""


def read(ctx):
    tr = ctx["trace"]
    if tr is None or not tr["busy_s_chip0"]:
        return None
    k = sum(v["seconds"] for v in tr["kernels"].values())
    return 100.0 * k / tr["busy_s_chip0"]
