"""Reduction of a JAX profiler trace (``.xplane.pb``) to intervals.

Device operations come from the ``XLA Ops`` line of each ``/device:TPU``
plane, whole programs from its ``XLA Modules`` line, and host spans from
the ``jax.profiler.TraceAnnotation`` names the client puts around its
calls. All times are nanoseconds on the profiler's clock; ``window``
converts a host ``perf_counter`` interval onto it through the span
``bench.window``.
"""

from __future__ import annotations

import dataclasses
import glob
import os

HOST_SPANS = ("pump", "engine_step", "admission", "fanout", "submit",
              "bench.window")


@dataclasses.dataclass
class Trace:
    ops: list          # [(label, start_ns, end_ns)] device operations
    modules: list      # [(label, start_ns, end_ns)] device programs
    host: list         # [(name, start_ns, end_ns)] client spans
    devices: int       # device planes seen


def _label(ev) -> str:
    parts = [ev.name]
    for st in getattr(ev, "stats", ()) or ():
        try:
            k, v = st
        except (TypeError, ValueError):
            continue
        if isinstance(v, str) and k in ("long_name", "tf_op", "hlo_op",
                                        "hlo_module", "kernel_details",
                                        "name"):
            parts.append(v)
    return " ".join(parts)


def from_events(ops, modules, host, devices=1) -> Trace:
    return Trace(sorted(ops, key=lambda e: e[1]),
                 sorted(modules, key=lambda e: e[1]),
                 sorted(host, key=lambda e: e[1]), devices)


def load(trace_dir: str) -> Trace:
    """Read the newest ``.xplane.pb`` under ``trace_dir``."""
    from jax.profiler import ProfileData
    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    pd = ProfileData.from_file(paths[-1])
    ops, modules, host, devices = [], [], [], 0
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU"):
            devices += 1
            for line in plane.lines:
                dest = {"XLA Ops": ops, "XLA Modules": modules}.get(line.name)
                if dest is None:
                    continue
                for ev in line.events:
                    dest.append((_label(ev), int(ev.start_ns),
                                 int(ev.start_ns + ev.duration_ns)))
        elif plane.name.startswith("/host"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name in HOST_SPANS:
                        host.append((ev.name, int(ev.start_ns),
                                     int(ev.start_ns + ev.duration_ns)))
    return from_events(ops, modules, host, max(devices, 1))


def window(tr: Trace) -> tuple[int, int]:
    spans = [h for h in tr.host if h[0] == "bench.window"]
    if not spans:
        raise ValueError("trace has no bench.window span")
    return spans[0][1], spans[0][2]


def union(intervals, t0: int, t1: int) -> list[tuple[int, int]]:
    """Merged intervals clipped to [t0, t1]."""
    out: list[list[int]] = []
    for iv in sorted((max(s, t0), min(e, t1)) for _, s, e in intervals
                     if e > t0 and s < t1):
        if out and iv[0] <= out[-1][1]:
            out[-1][1] = max(out[-1][1], iv[1])
        else:
            out.append([iv[0], iv[1]])
    return [(s, e) for s, e in out]


def covered(merged, t0: int, t1: int) -> int:
    """Nanoseconds of [t0, t1] that ``merged`` intervals cover."""
    return sum(max(0, min(e, t1) - max(s, t0)) for s, e in merged)


def busy_ns(tr: Trace, t0: int, t1: int) -> int:
    return covered(union(tr.ops, t0, t1), t0, t1)


def time_of(events, needle: str, t0: int, t1: int) -> tuple[int, int]:
    """(summed ns, count) of events whose label contains ``needle`` and
    that start inside [t0, t1)."""
    total = n = 0
    for lab, s, e in events:
        if needle in lab and t0 <= s < t1:
            total += e - s
            n += 1
    return total, n


def gaps(tr: Trace, t0: int, t1: int) -> list[tuple[int, int]]:
    """Idle intervals of the device inside [t0, t1]."""
    out, cur = [], t0
    for s, e in union(tr.ops, t0, t1):
        if s > cur:
            out.append((cur, s))
        cur = max(cur, e)
    if cur < t1:
        out.append((cur, t1))
    return out


DEPTH = {"pump": 1, "submit": 1, "engine_step": 2, "fanout": 2,
         "admission": 3}


def host_label(tr: Trace, s: int, e: int) -> str:
    """What the host was doing while the device idled over [s, e]: the
    innermost client span that holds the gap's midpoint."""
    mid = (s + e) // 2
    best, depth = "outside client spans", 0
    for name, hs, he in tr.host:
        d = DEPTH.get(name, 0)
        if d > depth and hs <= mid < he:
            best, depth = name, d
    return best


def breakdown(tr: Trace, t0: int, t1: int, top: int = 10) -> dict:
    """The device operations that took most time and the longest idle
    gaps, labelled by what the host was doing, as [name, seconds]."""
    per_op: dict[str, int] = {}
    for lab, s, e in tr.ops:
        if t0 <= s < t1:
            name = lab.split(" ")[0]
            per_op[name] = per_op.get(name, 0) + (e - s)
    ops = sorted(per_op.items(), key=lambda kv: -kv[1])[:top]
    idle = sorted(gaps(tr, t0, t1), key=lambda g: g[0] - g[1])[:top]
    return {"device_ops": [[n, v / 1e9] for n, v in ops],
            "idle_gaps": [[host_label(tr, s, e), (e - s) / 1e9]
                          for s, e in idle]}
