"""Start-up: XLA compiles (or compile-cache loads) that ended inside the
measured window, from ``jax.monitoring``. Expected 0: every shape the
window uses is warmed in set-up."""


def read(run):
    return run.compiles.between(run.w0, run.w1)
