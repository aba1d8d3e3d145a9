"""Collectives: the part of the collectives' union during which no other
instruction runs on chip 0, over the traced window: what overlap or a
quantized collective could still win."""


def read(ctx):
    tr = ctx["trace"]
    if tr is None or not tr["comm_s"]:
        return None
    return 100.0 * tr["comm_exposed_s"] / tr["window_s"]
