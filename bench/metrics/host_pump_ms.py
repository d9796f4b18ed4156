"""Mean host wall time of one ``AsyncServer.step()`` spent outside
``ServingEngine.step()``: router tick, dispatch and event fan-out."""


def read(run):
    pumps = run.pumps
    if not pumps:
        return None
    return 1e3 * sum(p - e for p, e in pumps) / len(pumps)
