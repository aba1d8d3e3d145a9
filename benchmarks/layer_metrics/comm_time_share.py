"""Collectives: union of all-gather / reduce-scatter / all-reduce /
all-to-all / collective-permute instruction time on chip 0 over the traced
window."""


def read(ctx):
    tr = ctx["trace"]
    if tr is None or not tr["comm_s"]:
        return None
    return 100.0 * tr["comm_s"] / tr["window_s"]
