#!/usr/bin/env python3
"""Smoke run of the PAM serving path on a TPU, at qwen3-0.6b's published
widths (28 layers, d_model 1024, 16 query / 8 kv heads of 128, bf16)
with random weights made from a seed.

    python chip_smoke.py              # one chip
    python chip_smoke.py --chips 4    # four chips: replicas and shard=4

One chip: a paged ring engine (block pool, hot-window ring, PAM on, no
latency model, so every time printed is a wall time) serves a handful of
requests through ``EngineSpec`` -> ``ServingEngine`` ->
``frontend.server.AsyncServer``, twice. The run checks that every request
got its full token count, that every token is in the vocabulary, that two
identical prompts in different slots got identical greedy streams, that
the second pass repeats the first, that the compiled fused decode step
holds a Pallas kernel (``tpu_custom_call``), and that the paged kernel
agrees with the jnp gather reference at the same widths.

``--chips 4``: four one-chip replicas behind ``ClusterRouter``, each on
its own chip, and the ``shard=4`` engine over all four must each match a
one-chip engine's greedy streams token for token (``run_fleet`` says
which one-chip engine, and why).

Everything runs in this one process, which holds the chips. Without a
TPU the script exits with code 2 and prints no result. Earlier lines of
standard output are JSON records of each phase; the last line is
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.
Any failed check raises, so the script then exits non-zero without it.
"""

from __future__ import annotations

import argparse
import asyncio
import dataclasses
import json
import os
import sys
import time

ARCH = "qwen3-0.6b"
# |kernel - reference| bound on the normalized attention output, whose
# entries are convex combinations of unit-normal values: fp32 accuracy
KERNEL_ATOL = 1e-4


class SmokeFailure(AssertionError):
    """A check of the smoke run failed."""


def _log(**record) -> None:
    print(json.dumps(record), flush=True)


def _require(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)


@dataclasses.dataclass(frozen=True)
class SmokeShape:
    """Traffic and engine geometry of the smoke run."""
    requests: int = 8
    prompt_len: int = 256          # prompts draw 3/4 to all of this
    new_tokens: int = 32
    max_len: int = 512
    block_size: int = 16
    hot_window: int = 128

    def serving_config(self):
        from repro.serving import PAMManagerConfig, ServingConfig
        pam = PAMManagerConfig(
            max_tokens=self.max_len,
            hot_capacity=max(self.max_len // 8, 8),
            warm_capacity=max(self.max_len // 4, 16),
            compression=4, recency_window=8, schedule_interval=2)
        return ServingConfig(max_batch=self.requests, max_len=self.max_len,
                             pam=pam, block_size=self.block_size,
                             hot_window=self.hot_window)


class _CompileClock:
    """Seconds JAX spends tracing, lowering and compiling (or loading a
    compiled program from the persistent cache), and cache hits."""

    def __init__(self):
        self.seconds = 0.0
        self.cache_hits = 0

    def _duration(self, event, duration_secs, **_):
        if event.startswith("/jax/core/compile/"):
            self.seconds += duration_secs

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1

    def __enter__(self):
        from jax import monitoring
        monitoring.register_event_duration_secs_listener(self._duration)
        monitoring.register_event_listener(self._event)
        return self

    def __exit__(self, *exc):
        from jax import monitoring
        monitoring.unregister_event_duration_listener(self._duration)
        monitoring.unregister_event_listener(self._event)


def make_requests(vocab: int, shape: SmokeShape, seed: int):
    """Seeded prompts; the last request repeats the first one's prompt
    (the twin pair)."""
    import numpy as np
    from repro.serving import Request
    rng = np.random.default_rng(seed)
    lo = max(shape.prompt_len * 3 // 4, 1)
    prompts = [rng.integers(0, vocab, int(rng.integers(lo, shape.prompt_len
                                                       + 1)), np.int32)
               for _ in range(shape.requests - 1)]
    prompts.append(prompts[0].copy())
    return [Request(id=i, prompt=p,
                    max_new_tokens=shape.new_tokens)
            for i, p in enumerate(prompts)]


def serve(backend, requests) -> dict[int, list[int]]:
    """Serve ``requests`` through the async front end; rid -> tokens."""
    from repro.frontend.server import AsyncServer
    srv = AsyncServer(backend)
    records = asyncio.run(srv.serve_trace(requests))
    return {rid: list(rec.tokens) for rid, rec in records.items()}


def check_streams(streams, requests, vocab: int) -> int:
    """Full token counts, in-vocabulary tokens; returns tokens served."""
    for r in requests:
        toks = streams.get(r.id, [])
        _require(len(toks) == r.max_new_tokens,
                 f"request {r.id}: {len(toks)} of {r.max_new_tokens} tokens")
        _require(all(0 <= t < vocab for t in toks),
                 f"request {r.id}: token outside the vocabulary")
    return sum(len(streams[r.id]) for r in requests)


def divergences(got, want, requests) -> dict[int, int]:
    """rid -> index of the first token where ``got`` leaves ``want``."""
    out = {}
    for r, w in zip(requests, want):
        g = got[r.id]
        if g != w:
            out[r.id] = next((i for i, (a, b) in enumerate(zip(g, w))
                              if a != b), min(len(g), len(w)))
    return out


def check_paged_kernel(cfg, shape: SmokeShape, seed: int) -> float:
    """``flash_decode_paged`` against the jnp gather reference
    (``paged_decode_attention_partial(use_kernel=False)``, fp32 matmuls)
    at ``cfg``'s widths; returns the max abs error of the normalized
    output."""
    import functools

    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.core import online_softmax as osm
    from repro.kernels import ops

    B, H, Hkv, d = shape.requests, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    bs = shape.block_size
    nb = shape.max_len // bs
    dt = jnp.dtype(cfg.dtype)
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    q = jax.random.normal(ks[0], (B, H, d), dt)
    pk = jax.random.normal(ks[1], (B * nb + 1, bs, Hkv, d), dt)
    pv = jax.random.normal(ks[2], (B * nb + 1, bs, Hkv, d), dt)
    table = jax.random.permutation(ks[3], B * nb).reshape(B, nb)
    mask = jax.random.uniform(ks[4], (B, nb * bs)) < 0.5
    mask = mask.at[:, bs:2 * bs].set(False)        # a dead block per row
    mask = mask.at[-1].set(False)                  # a row with no token

    def attend(use_kernel):
        part = ops.paged_decode_attention_partial(
            q, pk, pv, table, mask, use_kernel=use_kernel)
        return osm.finalize(part)

    got = jax.jit(functools.partial(attend, True))()
    with jax.default_matmul_precision("highest"):
        want = jax.jit(functools.partial(attend, False))()
    got, want = np.asarray(got), np.asarray(want)
    _require(np.isfinite(got).all(), "kernel output not finite")
    return float(np.max(np.abs(got - want)))


def peak_bytes(devices) -> list:
    """``peak_bytes_in_use`` per device (None where not reported)."""
    return [(d.memory_stats() or {}).get("peak_bytes_in_use")
            for d in devices]


def run_smoke(cfg, shape: SmokeShape = SmokeShape(), *, seed: int = 0,
              expect_kernel: bool = True) -> dict:
    """The one-chip smoke run: build, serve twice, check. Raises
    ``SmokeFailure`` on a failed check and returns the phase records.
    ``expect_kernel`` demands a Pallas call in the compiled decode step
    (the TPU path; off the chip the step takes the jnp reference)."""
    import jax
    from repro.models import transformer as tfm
    from repro.serving import EngineSpec

    dev = jax.devices()[0]
    out: dict = {}
    with _CompileClock() as clock:
        t0 = time.perf_counter()
        params = tfm.init_params(cfg, jax.random.PRNGKey(seed))
        engine = EngineSpec(model=cfg, serving=shape.serving_config(),
                            name="chip0").build(params)
        jax.block_until_ready(engine.params)
        out["build"] = {"arch": cfg.name, "layers": cfg.n_layers,
                        "dtype": cfg.dtype, "d_model": cfg.d_model,
                        "heads": cfg.n_heads, "kv_heads": cfg.n_kv_heads,
                        "head_dim": cfg.head_dim,
                        "params": cfg.param_count(),
                        "seconds": time.perf_counter() - t0}
        _log(phase="build", **out["build"])

        reqs = make_requests(cfg.vocab, shape, seed)
        streams, passes = [], []
        for n in range(2):
            batch = [dataclasses.replace(r, id=r.id + 1000 * n)
                     for r in reqs]
            before = clock.seconds
            t0 = time.perf_counter()
            got = serve(engine, batch)
            wall = time.perf_counter() - t0
            tokens = check_streams(got, batch, cfg.vocab)
            streams.append([got[r.id] for r in batch])
            passes.append({"pass": n, "requests": len(batch),
                           "tokens": tokens, "wall_s": wall,
                           "compile_s": clock.seconds - before})
            _log(phase="serve", **passes[-1])
        out["serve"] = passes
        _require(streams[0][0] == streams[0][-1],
                 "twin prompts in different slots gave different streams")
        _require(streams[1] == streams[0], "second pass differs from first")
        _log(phase="twins", identical=True, repeat_identical=True)

        text = engine.lower_decode_step().compile().as_text()
        out["kernel_in_step"] = "tpu_custom_call" in text
        _log(phase="decode_step", tpu_custom_call=out["kernel_in_step"])
        if expect_kernel:
            _require(out["kernel_in_step"],
                     "no Pallas kernel in the compiled fused decode step")

        err = check_paged_kernel(cfg, shape, seed)
        out["kernel_max_abs_err"] = err
        _log(phase="kernel_vs_reference", max_abs_err=err,
             tolerance=KERNEL_ATOL)
        _require(err <= KERNEL_ATOL,
                 f"flash_decode_paged off the reference by {err}")
    out["compile_s"] = clock.seconds
    out["cache_hits"] = clock.cache_hits
    out["peak_bytes_in_use"] = peak_bytes([dev])[0]
    _log(phase="setup", compile_s=clock.seconds, cache_hits=clock.cache_hits,
         peak_bytes_in_use=out["peak_bytes_in_use"])
    return out


def _one_chip_streams(cfg, scfg, params, device, requests, name):
    """Greedy streams of a one-chip engine pinned to ``device``."""
    from repro.serving import EngineSpec
    engine = EngineSpec(model=cfg, serving=scfg, name=name).build(
        params, devices=[device])
    got = serve(engine, requests)
    check_streams(got, requests, cfg.vocab)
    return [got[r.id] for r in requests]


def run_fleet(cfg, devices, shape: SmokeShape = SmokeShape(), *,
              seed: int = 0) -> dict:
    """``len(devices)`` one-chip replicas behind ``ClusterRouter`` and the
    ``shard=len(devices)`` engine, each against a one-chip engine's
    greedy streams on the same prompts.

    Greedy decoding of a random-weight model sits on near-ties, so a
    stream repeats only where the arithmetic does; each pair therefore
    runs the same programs on both sides. The replicas (``cfg.dtype``)
    each hold 1/n of the batch, so the router spreads the burst over all
    of them, and the one-chip engine has one replica's geometry and
    serves the requests in the same pairs. The ``shard`` engine's
    tensor-parallel params sum their partial products in another order
    than one chip; in bf16, or in float32 with the MXU's one-pass bf16
    products, that rounding alone flips near-ties, so this pair runs in
    float32 with full-precision matmuls, where the order moves results
    by fp32 rounding only."""
    import jax
    from repro.cluster import ClusterSpec
    from repro.cluster.spec import ReplicaGroup
    from repro.models import transformer as tfm
    from repro.perfmodel.devices import DeviceClass
    from repro.serving import EngineSpec

    n = len(devices)
    reqs = make_requests(cfg.vocab, shape, seed)
    out: dict = {}
    with _CompileClock() as clock:
        scfg = dataclasses.replace(shape.serving_config(),
                                   max_batch=max(shape.requests // n, 1))
        params = tfm.init_params(cfg, jax.random.PRNGKey(seed))
        ref = _one_chip_streams(cfg, scfg, params, devices[0], reqs, "one")
        group = ReplicaGroup(DeviceClass("tpu", max_batch=scfg.max_batch))
        router = ClusterSpec(model=cfg, groups=(group,) * n, serving=scfg,
                             wallclock=True).build(params)
        held = {d.name: [x.id for x in d.engine.devices]
                for d in router.devices}
        ids = [i for g in held.values() for i in g]
        _require(len(set(ids)) == n, f"replica groups share devices: {held}")
        got = serve(router, reqs)
        check_streams(got, reqs, cfg.vocab)
        tokens = {d.name: d.tokens_emitted for d in router.devices}
        out["replicas"] = held
        out["replica_divergences"] = divergences(got, ref, reqs)
        _log(phase="replicas", dtype=cfg.dtype, max_batch=scfg.max_batch,
             device_ids=held, token_identical=not out["replica_divergences"],
             first_divergence=out["replica_divergences"],
             tokens_by_replica=tokens)
        _require(all(tokens.values()), f"a replica served nothing: {tokens}")
        del router, params

        cfg32 = dataclasses.replace(cfg, dtype="float32")
        sspec = EngineSpec(model=cfg32, serving=shape.serving_config(),
                           shard=n, name="shard")
        blocks = sspec.total_pool_blocks()
        sspec = dataclasses.replace(sspec, serving=dataclasses.replace(
            sspec.serving, pool_blocks=-(-blocks // n) * n - 1)).validate()
        params = tfm.init_params(cfg32, jax.random.PRNGKey(seed))
        with jax.default_matmul_precision("highest"):
            ref = _one_chip_streams(cfg32, sspec.serving, params,
                                    devices[0], reqs, "one32")
            sharded = sspec.build(params, devices=devices)
            got = serve(sharded, reqs)
        check_streams(got, reqs, cfg.vocab)
        out["shard"] = [x.id for x in sharded.devices]
        out["shard_divergences"] = divergences(got, ref, reqs)
        _log(phase="shard", dtype=cfg32.dtype, matmul_precision="highest",
             shard=n, device_ids=out["shard"],
             token_identical=not out["shard_divergences"],
             first_divergence=out["shard_divergences"])
    out["compile_s"] = clock.seconds
    out["peak_bytes_in_use"] = peak_bytes(devices)
    _log(phase="setup", compile_s=clock.seconds, cache_hits=clock.cache_hits,
         peak_bytes_in_use=out["peak_bytes_in_use"])
    for what in ("replica", "shard"):
        _require(not out[f"{what}_divergences"],
                 f"{what} streams diverge from one chip: "
                 f"{out[f'{what}_divergences']}")
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: replicas and shard=4 against one chip only")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chip_smoke: needs a TPU; JAX found {devices[0].platform}",
              file=sys.stderr)
        return 2
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} but JAX found "
              f"{len(devices)} devices", file=sys.stderr)
        return 2

    from repro.launch.compile_cache import enable_compile_cache
    from repro.models.config import get_config
    _log(phase="start", platform=devices[0].platform,
         kind=devices[0].device_kind, count=len(devices),
         jax=jax.__version__, compile_cache=enable_compile_cache())
    cfg = get_config(ARCH)
    if args.chips == 4:
        run_fleet(cfg, devices[:4], seed=args.seed)
    else:
        run_smoke(cfg, seed=args.seed)
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.path.insert(0, os.path.join(os.path.dirname(
        os.path.abspath(__file__)), "src"))
    sys.exit(main())
