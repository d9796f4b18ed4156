"""Prefill operations of the real prompt tokens (padding excluded) over
the prefill programs' device time (``jit_pre``) times the bf16 peak."""


def read(run):
    total, n = run.module_time("jit_pre")
    if not n or not run.admitted:
        return None
    ops = sum(run.flops.prefill_flops(run.hf, p) for p in run.admitted)
    return 100.0 * ops / (total / 1e9 * run.peak["bf16_flops_per_s"])
