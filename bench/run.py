#!/usr/bin/env python3
"""Chip benchmark of the PAM serving path, one cell per run.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

A cell (``BENCHMARK.json`` ``workloads``) names a configuration
(``bench/configs/<config>.json``) and a traffic mix
(``bench/traffic/<traffic>.json``). The run makes the weights from the
seed on the device, builds ``EngineSpec`` -> ``ServingEngine`` ->
``ClusterRouter.for_engine`` -> ``AsyncServer`` on the wall clock, warms
every shape the traffic uses, and then acts as the client for
``--seconds``: it submits each request when it falls due, pumps the
server, and stamps each token as it arrives. Requests in the window are
served through a grace period after it. Then it frees the server and
checks a seeded sample of the requests that finished or streamed the
compared tokens, the longest among them, against the plain float32
reference (``bench/reference``): the widest and the mean gap by which
their served tokens' reference logits lie below the reference's best,
over the positions before PAM's participation is open to rounding, must
stay under the cell's limits (``bench/limits/<cell>.json``).

``--trace 0`` reports the cell's end-to-end metrics; ``--trace 1`` traces
a slice of the window with the JAX profiler and reports the per-layer
metrics (``bench/metrics/<name>.py``) and a breakdown. The last line of
standard output is one JSON object. Without a TPU, or with fewer chips
than the cell asks for, the run exits 2 and prints no result.
"""

from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import dataclasses  # noqa: E402
import gc  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
REPO_ROOT = os.path.dirname(BENCH_DIR)
for p in (REPO_ROOT, os.path.join(REPO_ROOT, "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

import numpy as np  # noqa: E402

from bench import flops as flops_mod  # noqa: E402
from bench import spec as spec_mod  # noqa: E402
from bench import stats, traffic  # noqa: E402
from bench import trace as trace_mod  # noqa: E402

WARM_ID = 4_000_000_000       # request ids of warm-up traffic (uint32)


def log(**rec) -> None:
    print(json.dumps(rec), flush=True)


class CompileClock:
    """Compiles (trace, lower, compile or cache load) seen by
    ``jax.monitoring``: count and seconds, since ``mark()``."""

    def __init__(self):
        self.seconds = 0.0
        self.events = 0
        self.cache_hits = 0

    def _duration(self, event, duration_secs, **_):
        if event.startswith("/jax/core/compile/backend_compile"):
            self.events += 1
        if event.startswith("/jax/core/compile/"):
            self.seconds += duration_secs

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1

    def install(self):
        from jax import monitoring
        monitoring.register_event_duration_secs_listener(self._duration)
        monitoring.register_event_listener(self._event)
        return self


def enable_cache() -> str:
    """JAX's persistent compilation cache: ``JAX_COMPILATION_CACHE_DIR``
    when set, else ``.jax_cache`` inside the checkout (a fixed path). Every
    program is kept, however fast it compiled."""
    import jax
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or os.path.join(
        REPO_ROOT, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_enable_compilation_cache", True)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return path


def load_peaks(kind: str) -> dict:
    with open(os.path.join(BENCH_DIR, "peaks.json")) as f:
        table = json.load(f)["devices"]
    if kind not in table:
        raise KeyError(f"device kind {kind!r} is not in bench/peaks.json")
    return table[kind]


def load_metric(name: str):
    path = os.path.join(BENCH_DIR, "metrics", name + ".py")
    sp = importlib.util.spec_from_file_location(f"bench_metric_{name}", path)
    mod = importlib.util.module_from_spec(sp)
    sp.loader.exec_module(mod)
    return mod.read


def load_reference(hf: dict):
    from importlib import import_module
    return import_module(f"bench.reference.{hf['reference']}")


# ------------------------------------------------------------------ serving
@dataclasses.dataclass
class Served:
    """What one window produced, kept after the server is freed."""
    in_scope: dict          # rid -> Req
    tokens: dict            # rid -> list[int] streamed by the end
    finished: set
    times: dict             # rid -> token arrival times
    due: dict               # rid -> due time
    window: float
    grace_end: float
    lateness: list
    window_compiles: int
    memory_peak: int | None
    pool: dict
    setup_phases: dict
    rejected: set


def build_server(cell, params, *, devices=None):
    from repro.cluster.router import ClusterRouter
    from repro.frontend.server import AsyncServer
    from repro.serving import EngineSpec
    mcfg = spec_mod.model_config(cell.hf, cell.config_name)
    scfg = spec_mod.serving_config(cell.traffic)
    engine = EngineSpec(model=mcfg, serving=scfg, name="chip0").build(
        params, devices=devices)
    return engine, AsyncServer(ClusterRouter.for_engine(engine))


def warm_requests(cell, rng):
    """Requests that make the engine compile every (prefill bucket,
    admission group size) the cell's traffic uses, and its decode step."""
    vocab = cell.hf["vocab_size"]
    groups = []
    k = 0
    for bucket, sizes in cell.traffic["warm"].items():
        plen = int(bucket) - 2
        for n in sizes:
            g = []
            for _ in range(n):
                g.append(traffic.Req(
                    id=WARM_ID + k, due=0.0, max_new=2,
                    prompt=rng.integers(0, vocab, plen).astype(np.int32)))
                k += 1
            groups.append(g)
    return groups


def serve_cell(cell, params, seed: int, seconds: float, *, trace=False,
               clock=None, warm=True):
    """Set up, warm (unless this process already ran the cell's shapes),
    run the window and the grace period. Returns (Served, RunData or
    None, setup seconds)."""
    import jax
    from bench.client import Client
    phases = {"to_server": time.perf_counter() - T_PROCESS}
    engine, srv = build_server(cell, params)
    client = Client(srv, engine, instrument=trace)
    rng = np.random.default_rng(seed)
    t = time.perf_counter()
    for group in warm_requests(cell, rng) if warm else ():
        for r in group:
            client.submit(r)
        client.run_until_done([r.id for r in group])
    phases["warm"] = time.perf_counter() - t
    tr = cell.traffic
    reqs = traffic.make_requests(
        tr, seed=seed, vocab=cell.hf["vocab_size"],
        max_len=tr["serving"]["max_len"], seconds=seconds)
    first: list = []
    if tr["loop"] == "closed":
        first, reqs = reqs[:tr["clients"]], reqs[tr["clients"]:]
        t = time.perf_counter()
        client.serve_one_by_one(first)
        phases["first_wave"] = time.perf_counter() - t
    jax.block_until_ready(engine.cache)
    # window opens: re-base the client's clock
    t_open = time.perf_counter()
    shift = client.t0 - t_open
    client.t0 = t_open
    for rid in client.times:
        client.times[rid] = [t + shift for t in client.times[rid]]
        client.due[rid] += shift
    setup_s = t_open - T_PROCESS
    compiles_before = clock.events if clock else 0

    tracer = _Tracer(client, seconds, tr) if trace else None
    grace = float(tr["grace_seconds"])
    if tr["loop"] == "open":
        grace_end = client.open_loop(reqs, seconds, grace,
                                     on_time=tracer and tracer.tick)
        in_scope = {r.id: r for r in reqs if r.due < seconds}
    else:
        grace_end = client.closed_loop(reqs, seconds, grace,
                                       on_time=tracer and tracer.tick)
        in_scope = {r.id: r for r in first}
        in_scope.update({r.id: r for r in reqs if r.id in client.submitted})
    if tracer is not None:
        tracer.stop()
    window_compiles = (clock.events - compiles_before) if clock else 0
    dev = jax.devices()[0]
    peak = (dev.memory_stats() or {}).get("peak_bytes_in_use")
    pool = {"pool_blocks": engine.allocator.num_blocks,
            "full_blocks": engine.scfg.max_batch * engine.scfg.max_len
            // engine.block_size,
            "peak_occupancy": engine.peak_occupancy}
    tokens = {rid: list(srv.records[rid].tokens) for rid in in_scope}
    served = Served(in_scope=in_scope, tokens=tokens,
                    finished=set(client.finished) & set(in_scope),
                    times={rid: client.times.get(rid, []) for rid in in_scope},
                    due={rid: client.due.get(rid, 0.0) for rid in in_scope},
                    window=float(seconds), grace_end=grace_end,
                    lateness=client.lateness,
                    window_compiles=window_compiles, memory_peak=peak,
                    pool=pool, setup_phases=phases,
                    rejected={r for r in in_scope if srv.records[r].rejected})
    run_data = tracer.run_data(cell) if tracer is not None else None
    # the client's instrumentation and the engine refer to each other
    del client, srv, engine, tracer
    gc.collect()
    return served, run_data, setup_s


class _Tracer:
    """Traces a slice of the window (centred, ``trace_seconds`` long)."""

    def __init__(self, client, seconds, tr):
        self.client = client
        span = min(float(tr.get("trace_seconds", 4.0)), seconds)
        self.start_at = max((seconds - span) / 2, 0.0)
        self.stop_at = self.start_at + span
        self.dir = tempfile.mkdtemp(prefix="bench_trace_")
        self.state = "before"
        self.ann = None
        self.trace = None

    def tick(self, now):
        import jax
        if self.state == "before" and now >= self.start_at:
            jax.profiler.start_trace(self.dir)
            self.ann = jax.profiler.TraceAnnotation("bench.window")
            self.ann.__enter__()
            self.client.recording = True
            self.state = "on"
        elif self.state == "on" and now >= self.stop_at:
            self.stop()

    def stop(self):
        import jax
        if self.state != "on":
            return
        self.client.recording = False
        self.ann.__exit__(None, None, None)
        jax.profiler.stop_trace()
        self.state = "done"
        self.trace = trace_mod.load(self.dir)
        shutil.rmtree(self.dir, ignore_errors=True)

    def run_data(self, cell):
        if self.trace is None:
            raise RuntimeError("the traced slice never started")
        c = self.client
        return RunData(cell=cell, hf=cell.hf, trace=self.trace,
                       pumps=list(c.pumps), steps=list(c.steps),
                       admitted=list(c.admitted))


@dataclasses.dataclass
class RunData:
    """What a per-layer reader sees of a traced run."""
    cell: object
    hf: dict
    trace: object
    pumps: list             # [(pump wall s, engine wall s)]
    steps: list             # engine.step records in the traced slice
    admitted: list          # prompt lengths first answered in the slice
    peak: dict = None
    flops: object = flops_mod

    def span(self):
        return trace_mod.window(self.trace)

    def module_time(self, needle):
        return trace_mod.time_of(self.trace.modules, needle, *self.span())

    def op_time(self, needle):
        return trace_mod.time_of(self.trace.ops, needle, *self.span())

    def _in_flight(self):
        """Pump spans merged across the short gaps between pumps: the
        time in which a request was queued or running."""
        t0, t1 = self.span()
        pumps = sorted((s, e) for n, s, e in self.trace.host if n == "pump")
        merged: list[list[int]] = []
        for s, e in pumps:
            if merged and s - merged[-1][1] <= 2_000_000:
                merged[-1][1] = max(merged[-1][1], e)
            else:
                merged.append([s, e])
        return [(max(s, t0), min(e, t1)) for s, e in merged
                if e > t0 and s < t1]

    def in_flight_ns(self):
        return sum(e - s for s, e in self._in_flight())

    def busy_in_flight_ns(self):
        t0, t1 = self.span()
        merged = trace_mod.union(self.trace.ops, t0, t1)
        return sum(trace_mod.covered(merged, s, e)
                   for s, e in self._in_flight())


# -------------------------------------------------------------- correctness
def sample_for_check(served: Served, k: int, seed: int,
                     positions: int) -> list[int]:
    """``k`` requests drawn from the seed among those that finished or
    streamed at least the ``positions`` tokens compared, the longest
    among them."""
    done = sorted(r for r, t in served.tokens.items()
                  if r in served.finished or len(t) >= positions)
    if not done:
        return []
    size = {r: len(served.in_scope[r].prompt) + len(served.tokens[r])
            for r in done}
    longest = max(done, key=lambda r: (size[r], -r))
    rest = [r for r in done if r != longest]
    rng = np.random.default_rng([seed, 1])
    pick = list(rng.choice(rest, size=min(k - 1, len(rest)), replace=False))
    return [longest] + [int(r) for r in pick]


def check(cell, params_fn, served: Served, seed: int, *, controls=()):
    """Stream integrity of every finished request, and the reference's
    logit gaps of the served tokens of a seeded sample of requests (the
    longest among them), over the positions the reference can compare
    (``bench/reference``): their widest and their mean gap, each against
    its limit. ``params_fn()`` returns the weights (called again after a
    control, which needs the device alone). Returns (checks, extra); with
    ``controls`` ("int8", "fp8") ``extra["controls"]`` holds each
    control's numbers over the same positions."""
    vocab = cell.hf["vocab_size"]
    bad = sum(1 for r, toks in served.tokens.items()
              if (r in served.finished
                  and len(toks) != served.in_scope[r].max_new)
              or any(not 0 <= t < vocab for t in toks))
    tr = cell.traffic
    lim = cell.limits
    k = int(lim["positions"])
    rids = sample_for_check(served, int(tr["reference_requests"]), seed, k)
    chunk = min(512, 1 << (int(tr["prompt"]["hi"]) - 1).bit_length())
    p_pad = -(-tr["prompt"]["hi"] // chunk) * chunk
    ref = load_reference(cell.hf)
    t = time.perf_counter()
    block = int(tr.get("reference_block", len(rids) or 1))
    bp, cmp, cbp = [], [], {c: [] for c in controls}
    for b in range(0, len(rids), block):
        # every block has ``block`` rows (the last padded with repeats of
        # its first), so that one compiled reference serves them all
        rows = rids[b:b + block]
        rows = rows + rows[:1] * (block - len(rows))
        out = ref.served_token_gaps(
            cell.hf, params_fn,
            [served.in_scope[r].prompt for r in rows],
            [np.asarray(served.tokens[r][:k], np.int32) for r in rows],
            pam=_pam_rules(tr), rtol=float(lim["importance_rtol"]),
            p_pad=p_pad, chunk=chunk,
            s_max=min(p_pad + k, tr["serving"]["max_len"]), a_max=k,
            controls=tuple(controls))
        n = min(block, len(rids) - b)
        bp.append(out["by_position"][:n])
        cmp.append(out["compared"][:n])
        for c in controls:
            cbp[c].append(out["control_by_position"][c][:n])
    ref_s = time.perf_counter() - t
    checks = {}
    if rids:
        bp, cmp = np.concatenate(bp), np.concatenate(cmp)
        nums = ref.compare(bp, cmp)
    else:
        nums = {name: float("inf") for name in ("max_logit_gap",
                                                "mean_logit_gap")}
    for name, v in nums.items():
        checks[name] = {"value": v, "limit": float(lim[name])}
    checks["bad_streams"] = {"value": bad, "limit": 0}
    checks["checked_requests"] = {"value": len(rids), "limit": 1}
    extra = {"reference_s": ref_s, "positions": k,
             "checked_tokens": int(np.sum(cmp)) if rids else 0,
             "compared": [int(c) for c in cmp] if rids else [],
             "by_position": [[round(float(g), 4) for g in row]
                             for row in bp] if rids else []}
    if controls and rids:
        extra["controls"] = {c: ref.compare(np.concatenate(cbp[c]), cmp)
                             for c in controls}
        extra["control_by_position"] = {
            c: [[round(float(g), 4) for g in row]
                for row in np.concatenate(cbp[c])] for c in controls}
    return checks, extra


def _pam_rules(tr: dict) -> dict:
    p = tr["serving"]["pam"]
    return {"compression": p["compression"],
            "recency_window": p["recency_window"], "lam": p["lam"]}


def failed_count(cell, served: Served) -> int:
    """Requests the server rejected, and in an open loop those that got no
    token by the end of grace. A closed-loop client waits for its reply
    without a deadline: a request still queued when the window closes is
    late, not failed."""
    n = len(served.rejected)
    if cell.traffic["loop"] == "open":
        n += sum(1 for r in served.in_scope
                 if r not in served.rejected and not served.times.get(r))
    return n


def is_correct(checks: dict) -> bool:
    """Every number within its limit; at least one request checked."""
    c = checks
    return (c["checked_requests"]["value"] >= c["checked_requests"]["limit"]
            and all(v["value"] <= v["limit"] for name, v in c.items()
                    if name != "checked_requests"))


# ------------------------------------------------------------------ metrics
def end_to_end(cell, served: Served, setup_s: float) -> dict:
    w = served.window
    times = served.times
    vals = {"setup_s": setup_s,
            "out_tok_s": stats.window_tokens(times, w) / w}
    gaps = stats.window_gaps(times, w)
    if gaps:
        vals["itl_p95_ms"] = 1e3 * stats.percentile(gaps, 95)
    due = {r: d for r, d in served.due.items()}
    tt, _ = stats.ttfts(due, times, w, served.grace_end)
    if tt:
        vals["ttft_p50_ms"] = 1e3 * stats.percentile(tt, 50)
        vals["ttft_p95_ms"] = 1e3 * stats.percentile(tt, 95)
    return {m["name"]: {"value": vals[m["name"]], "unit": m["unit"]}
            for m in cell.end_to_end if m["name"] in vals}


def per_layer(cell, run: RunData) -> dict:
    out = {}
    for m in cell.per_layer:
        v = load_metric(m["name"])(run)
        if v is not None:
            out[m["name"]] = {"value": float(v), "unit": m["unit"]}
    return out


# --------------------------------------------------------------------- main
def require_chips(chips: int):
    """The devices of a run, or None (with the reason on stderr) when JAX
    finds no TPU or fewer than ``chips``."""
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        print(f"bench: needs a TPU; JAX found {devs[0].platform}",
              file=sys.stderr)
        return None
    if len(devs) < chips:
        print(f"bench: the cell needs {chips} chips; JAX found {len(devs)}",
              file=sys.stderr)
        return None
    return devs


def run(cell, seed: int, seconds: float, trace: bool, devs) -> dict:
    import jax
    from bench import weights
    clock = CompileClock().install()
    cache_dir = enable_cache()
    peak_table = load_peaks(devs[0].device_kind)
    params = weights.make_params(cell.hf, seed)
    jax.block_until_ready(params)
    served, run_data, setup_s = serve_cell(cell, params, seed, seconds,
                                           trace=trace, clock=clock)
    lat = served.lateness
    log(phase="window", cell=cell.name, seed=seed, seconds=seconds,
        trace=trace, setup_s=setup_s, compile_cache=cache_dir,
        setup_compile_s=clock.seconds, cache_hits=clock.cache_hits,
        window_compiles=served.window_compiles,
        generator_late_p50_ms=1e3 * float(np.median(lat)) if lat else 0.0,
        generator_late_max_ms=1e3 * float(np.max(lat)) if lat else 0.0,
        in_scope=len(served.in_scope), finished=len(served.finished),
        grace_end_s=served.grace_end, setup_phases=served.setup_phases,
        **served.pool)
    checks, extra = check(cell, lambda: params, served, seed)
    log(phase="check", **extra)
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs), "memory_peak_bytes": served.memory_peak}
    result = {"correct": is_correct(checks),
              "attempted": len(served.in_scope),
              "failed": failed_count(cell, served)}
    if trace:
        run_data.peak = peak_table
        t0, t1 = run_data.span()
        device["busy_s"] = trace_mod.busy_ns(run_data.trace, t0, t1) / 1e9
        device["window_s"] = (t1 - t0) / 1e9
        result["metrics"] = per_layer(cell, run_data)
        result["breakdown"] = trace_mod.breakdown(run_data.trace, t0, t1)
    else:
        result["metrics"] = end_to_end(cell, served, setup_s)
    result["device"] = device
    result["checks"] = checks
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    cell = spec_mod.load_cell(args.workload)
    devs = require_chips(cell.chips)
    if devs is None:
        return 2
    result = run(cell, args.seed, args.seconds, bool(args.trace), devs)
    for name, c in result["checks"].items():
        print(f"check {name}: {c['value']} (limit {c['limit']})",
              file=sys.stderr)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
