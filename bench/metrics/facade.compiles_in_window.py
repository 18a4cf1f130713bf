"""Backend compiles that JAX reported during the window; it should read
0, since set-up calls every shape the window uses.  Layer: facade and
engines."""


def read(run):
    return run.window.compiles
