"""Device time of collective operations (all-reduce, all-gather,
reduce-scatter, collective-permute, all-to-all) inside the fused decode
programs (``jit_run_k``), per decode step and per device. An op counts
when it starts inside one of its device's ``jit_run_k`` programs; an
asynchronous collective's ``-start`` and ``-done`` ops each count for
their own duration (the compute that overlaps the transfer between them
does not), as one union on each device. None when the decode programs
ran no collective."""

from bench import trace as trace_mod


def read(run):
    t0, t1 = run.span()
    per_step, found = [], False
    for plane in run.trace.planes:
        progs = [(s, e) for lab, s, e in plane.modules
                 if "jit_run_k" in lab and t0 <= s < t1]
        if not progs:
            continue
        coll = [op for op in plane.ops if trace_mod.is_collective(op[0])
                and any(s <= op[1] < e for s, e in progs)]
        found = found or bool(coll)
        ns = sum(e - s for s, e in trace_mod.union(coll, t0, t1))
        per_step.append(ns / len(progs))
    if not found:
        return None
    return trace_mod.mean(per_step) / 1e6
