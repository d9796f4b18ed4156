"""Benchmark harness entry point — one section per paper table/figure.

Prints ``name,us_per_call,derived`` CSV. Run:
    PYTHONPATH=src python -m benchmarks.run [--section figs|kernels|engine|roofline]

``--out BENCH.json`` additionally records the machine-readable bench
trajectory point for the PR: real decode tokens/s of the serving fast path
and device dispatches per decode step (the fused-dispatch invariant).
"""

from __future__ import annotations

import argparse
import json


def main(argv=None) -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--section", default="all",
                    choices=["all", "figs", "kernels", "engine",
                             "roofline", "cluster", "chaos", "prefix",
                             "serving", "shard"])
    ap.add_argument("--dryrun-dir", default="experiments/dryrun")
    ap.add_argument("--out", default=None, metavar="BENCH.json",
                    help="write decode tokens/s + dispatch counts (and all "
                         "section rows) as JSON — the bench trajectory")
    args = ap.parse_args(argv)
    if args.out:              # fail fast, not after minutes of benching
        open(args.out, "a").close()

    rows: list[tuple] = []
    wallclock = None
    hot_scaling = None
    if args.section in ("all", "figs"):
        from benchmarks import paper_figs
        rows += paper_figs.fig9_online_slo()
        rows += paper_figs.fig10_offline()
        rows += paper_figs.fig11_energy()
        rows += paper_figs.fig12_ablation()
        rows += paper_figs.fig13_scalability()
        rows += paper_figs.headline_claims()
    if args.section in ("all", "kernels"):
        from benchmarks.kernel_bench import bench_kernels
        rows += bench_kernels()
    if args.section in ("all", "engine"):
        from benchmarks import engine_bench
        rows += engine_bench.bench_engine()
        wallclock = engine_bench.bench_decode_wallclock()
        rows += engine_bench.wallclock_rows(wallclock)
        hot_scaling = engine_bench.bench_hot_window_scaling()
        rows += engine_bench.hot_window_rows(hot_scaling)
    if args.section in ("all", "roofline"):
        from benchmarks.roofline import roofline_rows
        rows += roofline_rows(args.dryrun_dir)
    cluster = None
    if args.section in ("all", "cluster"):
        from benchmarks.cluster_bench import cluster_rows
        cluster, crows = cluster_rows()
        rows += crows
    chaos = None
    if args.section in ("all", "chaos"):
        from benchmarks.chaos_bench import chaos_rows
        chaos, xrows = chaos_rows()
        rows += xrows
    prefix = None
    if args.section in ("all", "prefix"):
        from benchmarks.prefix_bench import prefix_rows
        prefix, prows = prefix_rows()
        rows += prows
    serving = None
    if args.section in ("all", "serving"):
        from benchmarks.serving_bench import serving_rows
        serving, srows = serving_rows()
        rows += srows
    shard = None
    if args.section in ("all", "shard"):
        from benchmarks.shard_bench import shard_rows
        shard, shrows = shard_rows()
        rows += shrows

    print("name,us_per_call,derived")
    for name, us, derived in rows:
        print(f"{name},{us:.2f},{derived}")

    if args.out:
        payload = {
            "rows": [{"name": n, "us_per_call": us, "derived": d}
                     for n, us, d in rows],
            "suite": {"section": args.section, "n_rows": len(rows)},
        }
        if cluster is not None:
            # heterogeneous-cluster trajectory point (paper §4.3):
            # 1 device vs 3-device cluster under the same bursty trace
            payload["cluster"] = cluster
            payload["cluster_tok_s"] = cluster["cluster_tok_s"]
            payload["cluster_best_single_tok_s"] = \
                cluster["best_single_tok_s"]
            payload["cluster_speedup_vs_best_single"] = \
                cluster["cluster_speedup_vs_best_single"]
            payload["cluster_migrations"] = cluster["migrations"]
        if prefix is not None:
            # prefix-sharing trajectory point (PR 7): prefill FLOPs
            # saved and pool occupancy vs prompt share ratio, token
            # streams pinned exact against the cache-off twin
            payload["prefix"] = prefix
            payload["prefix_tokens_lost"] = prefix["tokens_lost_total"]
            payload["prefix_flops_saved_at_half"] = \
                prefix["flops_saved_at_half"]
            payload["prefix_occupancy_drop"] = \
                prefix["occupancy_drop_lo_to_hi"]
        if serving is not None:
            # serving-under-load trajectory point (PR 8): TTFT/TPOT
            # tails + SLO attainment over seeded arrival traces, zero
            # lost/dup streamed tokens, chunked prefill cutting the
            # p99 TPOT tail at equal offered load
            payload["serving"] = serving
            payload["serving_slo_attainment"] = \
                serving["smoke_slo_attainment"]
            payload["serving_p99_ttft_s"] = serving["p99_ttft_s_worst"]
            payload["serving_tokens_lost"] = serving["tokens_lost_total"]
            payload["serving_chunked_p99_tpot_ratio"] = \
                serving["chunked_prefill"]["p99_tpot_ratio"]
        if shard is not None:
            # sharded-engine trajectory point (PR 10): twin-exact
            # streams at shard 1/2/4, one dispatch/step under
            # shard_map, ~1/N param bytes per device, and the Alg. 1
            # (O, m, l) merge's collective bytes flat in context
            payload["shard"] = shard
            payload["shard_tokens_lost"] = shard["tokens_lost_total"]
            payload["shard_dispatches_per_step"] = \
                shard["dispatches_per_step_max"]
            payload["shard_merge_bytes_flat"] = \
                shard["merge_bytes_flat"]
            payload["shard_param_bytes_ratio_2way"] = (
                shard["points"]["2"]["param_bytes_per_device"]
                / shard["points"]["1"]["param_bytes_per_device"])
        if chaos is not None:
            # fault-tolerance trajectory point (PR 6): goodput under an
            # injected device kill, token-exact vs the failure-free twin
            payload["chaos"] = chaos
            payload["chaos_tokens_lost"] = chaos["tokens_lost_total"]
            payload["chaos_kill_goodput_ratio"] = \
                chaos["kill_goodput_ratio"]
            payload["chaos_kill_recovery_latency_mean_s"] = \
                chaos["kill_recovery_latency_mean_s"]
        if wallclock is not None:
            payload["decode_wallclock"] = wallclock
            payload["decode_tok_s"] = wallclock["micro"]["decode_tok_s"]
            payload["dispatches_per_step"] = \
                wallclock["fused"]["dispatches_per_step"]
            paged = wallclock.get("paged")
            if paged is not None:
                # paged warm/cold gather: sparse-read + occupancy point
                payload["paged_blocks_touched_per_step"] = \
                    paged["blocks_touched_per_step"]
                payload["paged_blocks_window_per_step"] = \
                    paged["blocks_window_per_step"]
                payload["paged_page_read_fraction"] = \
                    paged["page_read_fraction"]
                payload["paged_pool_occupancy_peak"] = \
                    paged["pool_occupancy_peak"]
                payload["paged_decode_tok_s"] = paged["decode_tok_s"]
            ring = wallclock.get("ring")
            if ring is not None:
                # hot-window ring trajectory point (PR 5)
                payload["ring_decode_tok_s"] = ring["decode_tok_s"]
                payload["ring_hot_window"] = ring["hot_window"]
                payload["ring_hot_bytes_per_slot"] = \
                    ring["hot_bytes_per_slot"]
        if hot_scaling is not None:
            payload["hot_window_scaling"] = hot_scaling
            payload["hot_bytes_per_slot"] = \
                hot_scaling["hot_bytes_per_slot"]
            payload["hot_bytes_constant_across_smax"] = \
                hot_scaling["hot_bytes_constant_across_smax"]
        with open(args.out, "w") as f:
            json.dump(payload, f, indent=2, sort_keys=True)
        print(f"# wrote {args.out}")


if __name__ == "__main__":
    main()
