"""Device time per fused decode step (``jit_run_k``), from the trace."""


def read(run):
    total, n = run.module_time("jit_run_k")
    return total / n / 1e6 if n else None
