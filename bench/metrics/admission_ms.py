"""Device time of an admission: the prefill programs (``jit_pre``) and
the admission commits (``jit_admit_commit``) that ran in the window, as
one union, over the number of commits."""

from bench import trace as trace_mod


def read(run):
    t0, t1 = run.span()
    mods = run.trace.modules
    commits = [m for m in mods
               if "jit_admit_commit" in m[0] and t0 <= m[1] < t1]
    if not commits:
        return None
    both = [m for m in mods if "jit_pre" in m[0] or "jit_admit_commit" in m[0]]
    ns = sum(e - s for s, e in trace_mod.union(both, t0, t1))
    return ns / len(commits) / 1e6
