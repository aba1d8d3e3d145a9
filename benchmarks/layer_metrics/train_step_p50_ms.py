"""Train engine: median host-clock time of ``train_step`` ended by
``block_until_ready`` on the loss, over the window's steps."""

from benchmarks.lib.stats import median


def read(ctx):
    steps = ctx["loop"]["steps"]
    return median([(e - b) * 1e3 for b, e, _ in steps]) if steps else None
