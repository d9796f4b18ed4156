"""Reduction of a JAX profiler trace (``.xplane.pb``) to intervals.

Device operations come from the ``XLA Ops`` line of each ``/device:TPU``
plane, whole programs from its ``XLA Modules`` line, and host spans from
the ``jax.profiler.TraceAnnotation`` names the client puts around its
calls. Each device keeps its own plane: busy time, idle gaps and program
and op times are reduced one device at a time and then averaged over the
cell's devices, so that four chips each busy a quarter of the time read
25%, not the 100% of their union. All times are nanoseconds on the
profiler's clock; ``window`` converts a host ``perf_counter`` interval
onto it through the span ``bench.window``.
"""

from __future__ import annotations

import dataclasses
import glob
import os
import re

HOST_SPANS = ("pump", "engine_step", "admission", "fanout", "submit",
              "bench.window")

# HLO opcodes of the collectives; asynchronous ones run as a ``-start``
# and a ``-done`` op, each counted for its own duration
COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter",
               "collective-permute", "all-to-all")
_OPS = "|".join(COLLECTIVES)
_NAME_RE = re.compile(rf"^[%_]?({_OPS})(-start|-done)?(\.\d+)?$")
_OPCODE_RE = re.compile(rf"=\s*\S+\s+({_OPS})(-start|-done)?\(")
_PLANE_RE = re.compile(r"^/device:TPU:(\d+)$")


@dataclasses.dataclass
class Plane:
    """One device: its operations and its programs, sorted by start."""
    name: str
    ops: list          # [(label, start_ns, end_ns)]
    modules: list      # [(label, start_ns, end_ns)]


@dataclasses.dataclass
class Trace:
    planes: list       # [Plane], one per device of the cell
    host: list         # [(name, start_ns, end_ns)] client spans


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


def _plane(name, ops, modules) -> Plane:
    return Plane(name, sorted(ops, key=lambda e: e[1]),
                 sorted(modules, key=lambda e: e[1]))


def from_events(ops, modules, host) -> Trace:
    """A one-device trace."""
    return from_planes([(ops, modules)], host)


def from_planes(planes, host) -> Trace:
    """A trace of several devices: ``planes`` is [(ops, modules)]."""
    return Trace([_plane(f"/device:TPU:{i}", o, m)
                  for i, (o, m) in enumerate(planes)],
                 sorted(host, key=lambda e: e[1]))


def load(trace_dir: str, device_ids=None) -> Trace:
    """Read the newest ``.xplane.pb`` under ``trace_dir``, keeping the
    planes of the devices ``device_ids`` (all TPU planes when None)."""
    from jax.profiler import ProfileData
    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    pd = ProfileData.from_file(paths[-1])
    keep = None if device_ids is None else {int(i) for i in device_ids}
    planes, host = [], []
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU"):
            m = _PLANE_RE.match(plane.name)
            if keep is not None and m and int(m.group(1)) not in keep:
                continue
            ops, modules = [], []
            for line in plane.lines:
                dest = {"XLA Ops": ops, "XLA Modules": modules}.get(line.name)
                if dest is None:
                    continue
                for ev in line.events:
                    dest.append((_label(ev), int(ev.start_ns),
                                 int(ev.start_ns + ev.duration_ns)))
            planes.append(_plane(plane.name, ops, modules))
        elif plane.name.startswith("/host"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name in HOST_SPANS:
                        host.append((ev.name, int(ev.start_ns),
                                     int(ev.start_ns + ev.duration_ns)))
    return Trace(planes or [_plane("/device:TPU:0", [], [])],
                 sorted(host, key=lambda e: e[1]))


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


def mean(values) -> float:
    values = list(values)
    return sum(values) / len(values)


def plane_busy_ns(plane: Plane, t0: int, t1: int) -> int:
    return covered(union(plane.ops, t0, t1), t0, t1)


def busy_ns(tr: Trace, t0: int, t1: int) -> float:
    """Device-busy nanoseconds of [t0, t1], the mean over the devices."""
    return mean(plane_busy_ns(p, t0, t1) for p in tr.planes)


def time_of(events, needle: str, t0: int, t1: int) -> tuple[int, int]:
    """(summed ns, count) of events whose label contains ``needle`` and
    that start inside [t0, t1)."""
    total = n = 0
    for lab, s, e in events:
        if needle in lab and t0 <= s < t1:
            total += e - s
            n += 1
    return total, n


def device_time_of(tr: Trace, kind: str, needle: str, t0: int,
                   t1: int) -> tuple[float, float]:
    """``time_of`` over each device's ``kind`` ("ops" or "modules"),
    (summed ns, count) as the mean over the devices."""
    per = [time_of(getattr(p, kind), needle, t0, t1) for p in tr.planes]
    return mean(t for t, _ in per), mean(n for _, n in per)


def is_collective(label: str) -> bool:
    """Whether an op is a collective, by its own instruction name or by
    the opcode in its HLO text (never by an operand's name)."""
    parts = label.split(" ", 1)
    return bool(_NAME_RE.match(parts[0])
                or (len(parts) > 1 and _OPCODE_RE.search(parts[1])))


def gaps(tr: Trace, t0: int, t1: int, plane: Plane | None = None
         ) -> list[tuple[int, int]]:
    """Idle intervals of one device (the first, unless ``plane``) inside
    [t0, t1]."""
    plane = tr.planes[0] if plane is None else plane
    out, cur = [], t0
    for s, e in union(plane.ops, t0, t1):
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


def busiest(tr: Trace, t0: int, t1: int) -> Plane:
    """The device with the most busy time in [t0, t1] (the first of
    equals)."""
    return max(tr.planes, key=lambda p: plane_busy_ns(p, t0, t1))


def breakdown(tr: Trace, t0: int, t1: int, top: int = 10) -> dict:
    """The busiest device's operations that took most time and its
    longest idle gaps, labelled by what the host was doing, as [name,
    seconds]."""
    plane = busiest(tr, t0, t1)
    per_op: dict[str, int] = {}
    for lab, s, e in plane.ops:
        if t0 <= s < t1:
            name = lab.split(" ")[0]
            per_op[name] = per_op.get(name, 0) + (e - s)
    ops = sorted(per_op.items(), key=lambda kv: -kv[1])[:top]
    idle = sorted(gaps(tr, t0, t1, plane), key=lambda g: g[0] - g[1])[:top]
    return {"device_ops": [[n, v / 1e9] for n, v in ops],
            "idle_gaps": [[host_label(tr, s, e), (e - s) / 1e9]
                          for s, e in idle]}
