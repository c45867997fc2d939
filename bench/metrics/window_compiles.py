"""window_compiles: XLA executables compiled or loaded from the compile
cache while the window ran (jax's backend-compile event); warm-up should
leave none."""


def read(run):
    return run.compiles
