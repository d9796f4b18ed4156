"""Share of the traced window with no device operation running, counted
only while at least one request was queued or running."""


def read(run):
    active = run.in_flight_ns()
    if active <= 0:
        return None
    return 100.0 * (1.0 - run.busy_in_flight_ns() / active)
