"""Process start to the start of the window: JAX and the chip coming up,
the request pool, loading or compiling each executable the window uses
and calling it once (host clock)."""


def read(run):
    return run.setup_s
