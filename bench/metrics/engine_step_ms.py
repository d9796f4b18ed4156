"""Mean host wall time of the ``ServingEngine.step()`` calls that
dispatched a decode step, admission and the readback that waits for the
device included."""


def read(run):
    ts = [s["dt"] for s in run.steps if s["active"] > 0]
    return 1e3 * sum(ts) / len(ts) if ts else None
