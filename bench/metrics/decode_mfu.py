"""Model operations of the traced decode steps over their device time
times the chip's bf16 peak: matmuls of the active sequences and attention
over the participating tokens."""


def read(run):
    total, n = run.module_time("jit_run_k")
    steps = [s for s in run.steps if s["active"] > 0]
    if not n or not steps:
        return None
    ops = sum(run.flops.decode_flops(run.hf, s["active"], s["reads"])
              for s in steps)
    # steps and programs are matched by count over the same window
    ops *= n / len(steps)
    return 100.0 * ops / (total / 1e9 * run.peak["bf16_flops_per_s"])
