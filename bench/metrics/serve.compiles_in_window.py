"""Backend compiles that JAX reported during an open loop's window and
drain: set-up sent each request shape through the service once, so what
compiles here are shapes the service makes of its own (cohort sizes,
chunk stops, queue lengths).  Layer: serving orchestrator."""


def read(run):
    return None if run.window.lateness is None else run.window.compiles
