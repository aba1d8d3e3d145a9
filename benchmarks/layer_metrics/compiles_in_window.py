"""Entry: XLA backend compiles inside the measured window (jax.monitoring
events).  Must be 0; a run where it is not prints ``correct: false``."""


def read(ctx):
    return ctx["compiles_in_window"]
