"""Device time of an admission: the prefill programs (``jit_pre``) and
the admission commits (``jit_admit_commit``) that ran in the window, as
one union on each device, over the number of commits (summed over the
devices, so a sharded admission counts once per device on both sides)."""

from bench import trace as trace_mod


def read(run):
    t0, t1 = run.span()
    ns = commits = 0
    for plane in run.trace.planes:
        mods = plane.modules
        n = sum(1 for m in mods
                if "jit_admit_commit" in m[0] and t0 <= m[1] < t1)
        if not n:
            continue
        both = [m for m in mods
                if "jit_pre" in m[0] or "jit_admit_commit" in m[0]]
        ns += sum(e - s for s, e in trace_mod.union(both, t0, t1))
        commits += n
    return ns / commits / 1e6 if commits else None
