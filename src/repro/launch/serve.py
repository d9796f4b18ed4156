"""End-to-end serving driver: the PAM engine under a synthetic request
stream, with the paper's timing model attached.

    PYTHONPATH=src python -m repro.launch.serve --arch qwen3-0.6b \
        --reduced --requests 16 --system pam

Multi-device cluster mode (paper §4.3) — route the stream across
heterogeneous devices with online KV balancing:

    PYTHONPATH=src python -m repro.launch.serve --arch qwen3-0.6b \
        --reduced --requests 32 --devices hbm:1,cxl:2 --block-size 8

Chaos mode — inject a deterministic fault trace (kills, stalls,
transfer corruption, pool exhaustion) and serve through it with the
recovery watchdog attached:

    PYTHONPATH=src python -m repro.launch.serve --arch qwen3-0.6b \
        --reduced --requests 32 --devices hbm:1,cxl:2 --block-size 8 \
        --chaos 'kill:cxl1@40,corrupt@20' --chaos-seed 0

Serving front-end mode (PR 8) — run a seeded arrival trace through the
async streaming server (``repro.frontend``) with chunked prefill and
SLO-aware admission, scoring TTFT/TPOT tails and SLO attainment:

    PYTHONPATH=src python -m repro.launch.serve --arch qwen3-0.6b \
        --reduced --serve --requests 64 --trace onoff --rate 200 \
        --block-size 8 --prefill-chunk 8 --slo-ttft-ms 250

``--port N`` additionally drives the trace through the line-delimited
JSON socket endpoint on 127.0.0.1:N (0 picks a free port) instead of
the in-process API — same tokens, exercised over the wire.

Telemetry (PR 9) — any mode: ``--trace-out trace.json`` records the
request-lifecycle/device-event trace (open trace.json at
https://ui.perfetto.dev) and ``--metrics-interval N`` streams live
registry snapshots as JSON lines; both print the final metrics
snapshot at exit:

    PYTHONPATH=src python -m repro.launch.serve --arch qwen3-0.6b \
        --reduced --requests 32 --devices hbm:1,cxl:2 --block-size 8 \
        --chaos 'kill:cxl1@40' --trace-out trace.json
"""

from __future__ import annotations

import argparse
import json

import jax
import numpy as np

from repro.obs import metrics as obs_metrics
from repro.obs import trace as obs_trace
from repro.perfmodel import make_latency_model
from repro.models import transformer as tfm
from repro.models.config import get_config, reduced
from repro.perfmodel.model import PAM_LLAMA_7B, SystemKind, make_system
from repro.serving import (EngineSpec, PAMManagerConfig, Request,
                           ServingConfig)


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen3-0.6b")
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--requests", type=int, default=16)
    ap.add_argument("--prompt-len", type=int, default=24)
    ap.add_argument("--gen-len", type=int, default=16)
    ap.add_argument("--max-batch", type=int, default=4)
    ap.add_argument("--max-len", type=int, default=128)
    ap.add_argument("--system", default="pam",
                    choices=[k.value for k in SystemKind] + ["wallclock"])
    ap.add_argument("--no-sparsity", action="store_true")
    ap.add_argument("--block-size", type=int, default=0,
                    help="paged warm/cold KV block tokens (0 = dense)")
    ap.add_argument("--pool-blocks", type=int, default=None,
                    help="physical pool blocks (default: no overcommit)")
    ap.add_argument("--hot-window", type=int, default=0,
                    help="hot-tier ring slots (0 = full window; requires "
                         "--block-size): per-slot HBM-tier bytes stop "
                         "scaling with --max-len")
    ap.add_argument("--devices", default=None, metavar="SPEC",
                    help="cluster mode: heterogeneous device spec, e.g. "
                         "'hbm:1,cxl:2' (see repro.perfmodel.devices)")
    ap.add_argument("--shard", type=int, default=1,
                    help="devices per replica group (PR 10): the fused "
                         "decode step runs shard_map'ed over this many "
                         "devices sharing ONE sharded param replica; "
                         "with --devices, same-class runs group by this "
                         "size (needs that many local/XLA host devices)")
    ap.add_argument("--arrival-gap-ms", type=float, default=2.0,
                    help="cluster mode: mean Poisson arrival gap")
    ap.add_argument("--chaos", default=None, metavar="SPEC",
                    help="cluster mode: fault trace, e.g. "
                         "'kill:hbm0@120,stall:cxl0@50x8,corrupt@30*2' "
                         "(see repro.cluster.faults)")
    ap.add_argument("--chaos-seed", type=int, default=0,
                    help="seed for injected corruption bytes")
    ap.add_argument("--temperature", type=float, default=0.0,
                    help="on-device sampling temperature (0 = greedy)")
    ap.add_argument("--top-k", type=int, default=0)
    ap.add_argument("--prefill-chunk", type=int, default=0,
                    help="chunked-prefill slice budget in tokens (pow-2; "
                         "0 = monolithic prefill; requires --block-size)")
    ap.add_argument("--serve", action="store_true",
                    help="front-end mode: stream a seeded arrival trace "
                         "through the async server (repro.frontend)")
    ap.add_argument("--trace", default="poisson",
                    choices=["poisson", "gamma", "onoff"],
                    help="--serve: arrival trace shape")
    ap.add_argument("--rate", type=float, default=200.0,
                    help="--serve: mean arrival rate (req/s)")
    ap.add_argument("--slo-ttft-ms", type=float, default=250.0,
                    help="--serve: time-to-first-token SLO")
    ap.add_argument("--slo-tpot-ms", type=float, default=50.0,
                    help="--serve: per-output-token SLO")
    ap.add_argument("--port", type=int, default=None,
                    help="--serve: drive the trace through the NDJSON "
                         "socket endpoint on this port (0 = ephemeral)")
    ap.add_argument("--trace-seed", type=int, default=0)
    ap.add_argument("--trace-out", default=None, metavar="PATH",
                    help="record request-lifecycle + device events and "
                         "write a Perfetto-loadable Chrome trace JSON "
                         "here at exit (enables the metrics registry)")
    ap.add_argument("--metrics-interval", type=int, default=0,
                    help="emit a live metrics snapshot JSON line every "
                         "N steps/ticks (0 = only the final snapshot; "
                         "any value enables the metrics registry)")
    args = ap.parse_args(argv)

    # telemetry (PR 9): install registry/collector BEFORE building
    # engines — instruments bind at construction time
    telemetry = bool(args.trace_out) or args.metrics_interval > 0
    if telemetry:
        obs_metrics.install()
    if args.trace_out:
        obs_trace.install()

    cfg = get_config(args.arch)
    if args.reduced:
        cfg = reduced(cfg)
    params = tfm.init_params(cfg, jax.random.PRNGKey(0))

    pam_cfg = None
    if cfg.has_decode:
        pam_cfg = PAMManagerConfig(
            max_tokens=args.max_len,
            hot_capacity=max(args.max_len // 8, 8),
            warm_capacity=max(args.max_len // 4, 16),
            compression=4, recency_window=8, schedule_interval=2,
            use_sparsity=not args.no_sparsity)

    if args.prefill_chunk and not args.block_size:
        ap.error("--prefill-chunk requires --block-size (paged KV)")
    scfg = ServingConfig(max_batch=args.max_batch, max_len=args.max_len,
                         pam=pam_cfg, block_size=args.block_size,
                         pool_blocks=args.pool_blocks,
                         hot_window=args.hot_window,
                         temperature=args.temperature, top_k=args.top_k,
                         prefill_chunk=args.prefill_chunk)
    rng = np.random.default_rng(0)

    try:
        if args.serve:                 # ---- front-end mode (PR 8)
            return _serve_mode(args, ap, cfg, params, scfg)
        return _batch_mode(args, ap, cfg, params, scfg, rng)
    finally:
        if telemetry:
            _finish_telemetry(args)


def _metrics_emit(tick: int) -> None:
    """One live metrics line (scalar series only; histograms land in
    the final snapshot)."""
    snap = obs_metrics.get_registry().snapshot()
    print(json.dumps({"op": "metrics", "tick": tick,
                      "counters": snap["counters"],
                      "gauges": snap["gauges"]}))


def _finish_telemetry(args) -> None:
    """Exit-time telemetry flush: final registry snapshot and (with
    ``--trace-out``) the balanced Chrome trace JSON."""
    reg = obs_metrics.get_registry()
    if reg.enabled:
        print(json.dumps({"op": "metrics", "final": True,
                          "metrics": reg.snapshot()}))
    tr = obs_trace.COLLECTOR
    if tr is not None and args.trace_out:
        tr.close_open()          # balanced even if work was in flight
        tr.write(args.trace_out)
        print(f"trace: {len(tr.events)} events "
              f"({tr.dropped} dropped) -> {args.trace_out}")


def _build_backend(args, ap, cfg, params, scfg, *,
                   recovery_default: bool = False):
    """(backend, engine-or-None): a ``ClusterRouter`` in ``--devices``
    mode, a bare ``ServingEngine`` otherwise — both speaking the PR 10
    unified surface (``as_router()`` / ``serve()``), so no caller
    special-cases the two. Construction goes through
    ``ClusterSpec``/``EngineSpec`` only."""
    if args.devices:                   # ---- cluster mode (paper §4.3)
        if args.system not in ("pam", "wallclock"):
            ap.error("--devices models PAM-class devices; --system must "
                     "be 'pam' (modeled, the default) or 'wallclock'")
        from repro.cluster import (BalancerConfig, ClusterSpec,
                                   FaultInjector, KVBalancer,
                                   RecoveryConfig)
        faults = rec_cfg = None
        if args.chaos:
            faults = FaultInjector.from_spec(args.chaos,
                                             seed=args.chaos_seed)
        if args.chaos or recovery_default:
            rec_cfg = RecoveryConfig()
        spec = ClusterSpec.from_cli(
            args.devices, model=cfg, serving=scfg, shard=args.shard,
            recovery=rec_cfg, wallclock=(args.system == "wallclock"))
        router = spec.build(params, balancer=KVBalancer(BalancerConfig()),
                            faults=faults)
        return router, None
    latency = None
    if args.system != "wallclock":
        latency = make_latency_model(make_system(args.system),
                                     PAM_LLAMA_7B)
    eng = EngineSpec(model=cfg, serving=scfg, shard=args.shard).build(
        params, latency_model=latency)
    return eng, eng


def _batch_mode(args, ap, cfg, params, scfg, rng) -> None:
    backend, engine = _build_backend(args, ap, cfg, params, scfg)
    router = backend.as_router()
    t = 0.0
    reqs = []
    for i in range(args.requests):
        if args.devices:
            t += float(rng.exponential(args.arrival_gap_ms / 1e3))
        reqs.append(Request(
            id=i, prompt=rng.integers(0, cfg.vocab, args.prompt_len),
            max_new_tokens=args.gen_len, arrival=t))
    if args.metrics_interval > 0:
        for req in reqs:
            router.submit(req)
        limit, n = router.rcfg.max_ticks, 0
        while router.tick():
            n += 1
            if n >= limit:
                raise RuntimeError(f"no drain in {limit} ticks")
            if n % args.metrics_interval == 0:
                _metrics_emit(n)
    else:
        # the unified streaming surface: one generator, engine or fleet
        for _ev in router.serve(reqs):
            pass
    summary = router.summary()
    if engine is not None:
        # single-device runs keep the engine-level detail keys (paged
        # stats, chunked-prefill counters, TPOT percentiles) alongside
        # the router view
        for k, v in engine.summary().items():
            summary.setdefault(k, v)
    print(json.dumps(summary, indent=1))
    for slo_ms in (100, 150, 200):
        print(f"SLO {slo_ms}ms attainment: "
              f"{router.slo_attainment(slo_ms/1e3):.3f}")


async def _pump_with_metrics(srv, trace, interval: int) -> None:
    """``serve_trace`` with a live metrics line every ``interval``
    pump iterations."""
    import asyncio

    for req in trace:
        srv.submit(req.prompt, req.max_new_tokens, rid=req.id,
                   arrival=req.arrival)
    limit, n = srv.router.rcfg.max_ticks, 0
    while srv.step():
        n += 1
        if n >= limit:
            raise RuntimeError(f"server did not drain in {limit} ticks")
        if n % interval == 0:
            _metrics_emit(n)
        if n % srv.ticks_per_yield == 0:
            await asyncio.sleep(0)


async def _drive_socket(srv, trace, port: int):
    """Replay the trace over the NDJSON endpoint: one loopback client
    per request, all token lines consumed (the wire-path variant of
    ``serve_trace`` — arrivals happen as connections land)."""
    import asyncio
    import json as _json

    server, bound, pump = await srv.serve_endpoint(port=port)

    async def one(req):
        reader, writer = await asyncio.open_connection("127.0.0.1", bound)
        writer.write((_json.dumps(
            {"id": req.id, "prompt": req.prompt.tolist(),
             "max_new_tokens": req.max_new_tokens}) + "\n").encode())
        await writer.drain()
        while True:
            line = await reader.readline()
            if not line or _json.loads(line)["done"]:
                break
        writer.close()

    try:
        await asyncio.gather(*(one(r) for r in trace))
    finally:
        pump.cancel()
        server.close()
        await server.wait_closed()
    return bound


def _serve_mode(args, ap, cfg, params, scfg) -> None:
    import asyncio

    from repro.frontend.admission import SLOAdmission, SLOSpec
    from repro.frontend.loadgen import TraceConfig, make_trace, score
    from repro.frontend.server import AsyncServer

    backend, _ = _build_backend(args, ap, cfg, params, scfg,
                                recovery_default=True)

    slo = SLOSpec(ttft_s=args.slo_ttft_ms / 1e3,
                  tpot_s=args.slo_tpot_ms / 1e3)
    trace = make_trace(TraceConfig(
        kind=args.trace, n_requests=args.requests, rate_rps=args.rate,
        prompt_len=(max(args.prompt_len // 2, 1), args.prompt_len),
        max_new=(max(args.gen_len // 2, 1), args.gen_len),
        vocab=cfg.vocab, seed=args.trace_seed))
    srv = AsyncServer(backend, admission=SLOAdmission(slo))

    port = None
    if args.port is None:
        if args.metrics_interval > 0:
            asyncio.run(_pump_with_metrics(srv, trace,
                                           args.metrics_interval))
        else:
            asyncio.run(srv.serve_trace(trace))
    else:
        port = asyncio.run(_drive_socket(srv, trace, args.port))

    sc = score(srv.records.values(), ttft_slo_s=slo.ttft_s,
               tpot_slo_s=slo.tpot_s)
    back = srv.router.summary()
    payload = {
        "mode": "serve",
        "trace": args.trace,
        "rate_rps": args.rate,
        "prefill_chunk": args.prefill_chunk,
        "port": port,
        "score": sc,
        "admission": srv.admission.summary(),
        "backend": {k: back[k] for k in
                    ("finished", "rejected", "total_tokens",
                     "makespan_s", "throughput_tok_s", "ticks")},
    }
    print(json.dumps(payload, indent=1))


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    main()
