"""Device: 1 - (union of instruction time) / traced window, chip 0: what
any host-side change can win."""


def read(ctx):
    tr = ctx["trace"]
    if tr is None:
        return None
    return 100.0 * (1.0 - tr["busy_s_chip0"] / tr["window_s"])
