"""Assert the engine-bench trajectory point is sane — perf regressions
fail loudly instead of silently landing.

    python scripts/check_bench.py BENCH.json [tok_s_floor]

Checks (engine section of ``benchmarks.run``):
  * one fused dispatch per decode step (the PR 1 invariant)
  * decode tokens/s above a catastrophic-regression floor
  * paged sparse read: pages touched < dense-window pages (PR 2)
  * hot-tier bytes/slot constant across max_len in {1k, 4k, 16k}
    (PR 5 ring invariant), and the ring within 10% of the full-window
    paged engine's tokens/s

Checks (chaos section, ``BENCH_pr6.json``):
  * zero tokens lost across every fault scenario (twin-exact recovery)
  * 1-kill goodput >= 0.8x the fault-free run of the same trace

Checks (prefix section, ``BENCH_pr7.json``):
  * zero tokens lost at EVERY share ratio (prefix sharing is exact)
  * prefill FLOPs saved > 0 wherever the share ratio >= 0.5
  * peak pool occupancy monotonically helped: occupancy at the highest
    share ratio below the no-sharing ratio's (shared blocks count once)

Checks (shard section, ``BENCH_pr10.json``):
  * zero tokens diverged between shard 1/2/4 engines (sharding is
    bit-exact)
  * one fused dispatch per decode step under shard_map
  * a 2-way-sharded engine holds <= 0.6x the full param copy per
    device (replica groups share one sharded replica)
  * the Alg. 1 (O, m, l) merge's collective bytes are FLAT in context

Checks (serving section, ``BENCH_pr8.json``):
  * zero lost / duplicated streamed tokens across every scenario
  * SLO attainment >= 0.9 on the smoke trace (single-device Poisson)
  * p99 TTFT on the smoke trace below the committed ceiling
  * chunked prefill cuts the pooled p99 token-gap tail on the
    long-prompt trace (ratio vs unchunked <= 0.9) at matched
    throughput (within 5%)
"""

import json
import sys


def check_chaos(d: dict) -> None:
    lost = d["chaos_tokens_lost"]
    ratio = d["chaos_kill_goodput_ratio"]
    assert lost == 0, (
        f"{lost} tokens lost under injected faults — recovery is no "
        f"longer twin-exact")
    assert ratio >= 0.8, (
        f"1-kill goodput ratio {ratio:.3f} below the 0.8 floor")
    print(f"chaos bench OK: 0 tokens lost, 1-kill goodput "
          f"{ratio:.3f}x fault-free (floor 0.8), recovery mean "
          f"{d['chaos_kill_recovery_latency_mean_s'] * 1e3:.1f} ms sim")


def check_prefix(d: dict) -> None:
    lost = d["prefix_tokens_lost"]
    assert lost == 0, (
        f"{lost} tokens diverged from the cache-off twin — prefix "
        f"sharing is no longer exact")
    points = d["prefix"]["points"]
    for p in points.values():
        assert p["tokens_lost"] == 0, p
        if p["share_ratio"] >= 0.5:
            assert p["prefill_flops_saved"] > 0, (
                f"no prefill compute saved at share ratio "
                f"{p['share_ratio']} — the trie stopped matching")
    ordered = sorted(points.values(), key=lambda p: p["share_ratio"])
    lo, hi = ordered[0], ordered[-1]
    assert hi["pool_occupancy_peak"] < lo["pool_occupancy_peak"], (
        f"peak occupancy did not drop with sharing: "
        f"{lo['pool_occupancy_peak']:.3f} @ r={lo['share_ratio']} vs "
        f"{hi['pool_occupancy_peak']:.3f} @ r={hi['share_ratio']}")
    print(f"prefix bench OK: 0 tokens lost over {len(points)} share "
          f"ratios, {hi['prefill_flops_saved']:.3g} prefill FLOPs saved "
          f"at r={hi['share_ratio']}, peak occupancy "
          f"{lo['pool_occupancy_peak']:.3f} -> "
          f"{hi['pool_occupancy_peak']:.3f}")


def check_serving(d: dict) -> None:
    lost = d["serving_tokens_lost"]
    assert lost == 0, (
        f"{lost} streamed tokens lost or duplicated — the server loop "
        f"broke the stream contract")
    att = d["serving_slo_attainment"]
    assert att >= 0.9, (
        f"smoke-trace SLO attainment {att:.3f} below the 0.9 floor")
    smoke = d["serving"]["scenarios"]["single_poisson"]
    p99 = smoke["ttft_s"]["p99"]
    # the sim clock is modeled and seeded, so this is deterministic;
    # the ceiling is ~5x the committed value (0.0037 s)
    assert p99 <= 0.02, (
        f"smoke-trace p99 TTFT {p99:.4f}s above the 0.02s ceiling")
    ratio = d["serving_chunked_p99_tpot_ratio"]
    assert ratio <= 0.9, (
        f"chunked prefill no longer cuts the p99 token-gap tail: "
        f"ratio {ratio:.3f} vs unchunked (floor 0.9)")
    cc = d["serving"]["chunked_prefill"]
    tc = cc["chunked"]["throughput_tok_s"]
    tu = cc["unchunked"]["throughput_tok_s"]
    assert abs(tc - tu) <= 0.05 * tu, (
        f"chunked/unchunked throughput diverged: {tc:.0f} vs {tu:.0f} "
        f"tok/s — the tail comparison is no longer at equal load")
    print(f"serving bench OK: 0 lost/dup tokens, smoke SLO {att:.3f} "
          f"(floor 0.9), p99 TTFT {p99 * 1e3:.2f} ms, chunked p99 "
          f"token-gap {ratio:.3f}x unchunked at {tc:.0f}/{tu:.0f} tok/s")


def check_shard(d: dict) -> None:
    lost = d["shard_tokens_lost"]
    assert lost == 0, (
        f"{lost} tokens diverged between sharded and unsharded "
        f"engines — the shard_map merge is no longer exact")
    disp = d["shard_dispatches_per_step"]
    assert disp == 1.0, (
        f"{disp} dispatches/step — sharding broke the fused-dispatch "
        f"invariant")
    ratio = d["shard_param_bytes_ratio_2way"]
    assert ratio <= 0.6, (
        f"2-way-sharded engine holds {ratio:.2f}x of the full param "
        f"copy per device (floor 0.6x) — replica groups no longer "
        f"share the replica")
    assert d["shard_merge_bytes_flat"] is True, (
        "the (O, m, l) merge's collective bytes grew with context — "
        "the flat-communication claim regressed")
    pts = d["shard"]["points"]
    print(f"shard bench OK: 0 tokens diverged at shard "
          f"{sorted(pts, key=int)}, {disp:.2f} dispatches/step, "
          f"{ratio:.2f}x param bytes/device at shard 2, merge "
          f"{d['shard']['merge_bytes_per_step']} B/step flat in "
          f"context")


def main(path: str, floor: float = 100.0) -> None:
    d = json.load(open(path))
    done = False
    if "prefix_tokens_lost" in d:
        check_prefix(d)
        done = True
    if "shard_tokens_lost" in d:
        check_shard(d)
        done = True
    if "chaos_kill_goodput_ratio" in d:
        check_chaos(d)
        done = True
    if "serving_slo_attainment" in d:
        check_serving(d)
        done = True
    if done and "dispatches_per_step" not in d:
        return                           # section-only bench file
    assert d["dispatches_per_step"] == 1.0, d["dispatches_per_step"]
    assert d["decode_tok_s"] > floor, (
        f"decode tok/s {d['decode_tok_s']:.0f} below floor {floor:.0f}")
    assert d["paged_blocks_touched_per_step"] < \
        d["paged_blocks_window_per_step"]
    assert d["hot_bytes_constant_across_smax"] is True, \
        d.get("hot_window_scaling")
    ring, paged = d["ring_decode_tok_s"], d["paged_decode_tok_s"]
    # catastrophic-only guard: single-run wall-clock on shared runners
    # jitters well past 10%, so CI asserts the ring is in the same class
    # as the full-window paged engine; the tighter 10% comparison is the
    # BENCH_pr5.json acceptance check, taken on a quiet machine
    assert ring > 0.5 * paged, (
        f"ring decode {ring:.0f} tok/s collapsed vs the full-window "
        f"paged engine's {paged:.0f}")
    scaling = d["hot_window_scaling"]["points"]
    print(f"bench OK: {d['decode_tok_s']:.0f} tok/s (floor {floor:.0f}), "
          f"{d['dispatches_per_step']:.2f} dispatches/step, paged pages/"
          f"step {d['paged_blocks_touched_per_step']:.1f}"
          f"/{d['paged_blocks_window_per_step']:.1f}, ring "
          f"{ring:.0f} tok/s at {d['hot_bytes_per_slot']} hot bytes/slot "
          f"constant over Smax {sorted(scaling, key=int)}")


if __name__ == "__main__":
    main(sys.argv[1],
         float(sys.argv[2]) if len(sys.argv) > 2 else 100.0)
