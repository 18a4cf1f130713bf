"""Share of an open loop's requests, in %, that came back answered; the
rest were rejected, expired, failed, raised or were unfinished when the
drain ended.  Layer: serving orchestrator."""


def read(run):
    if run.window.lateness is None or not run.window.calls:
        return None
    return 100.0 * len(run.done) / len(run.window.calls)
