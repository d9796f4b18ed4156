"""Per-layer metric readers: ``metrics/<name>.py`` each define
``read(run) -> float | None`` (None: nothing to read in this run).
``run`` is a ``bench.run.RunData``."""
