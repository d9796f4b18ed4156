#!/usr/bin/env python3
"""Rate sweep of an open-loop cell, to find the highest rate it sustains.

    python bench/sweep.py --workload <cell> --seconds <s> --rates 2 3 4 5

One process: the weights once, then for each rate a fresh server, the
cell's warm-up and a window at that rate (the cell's traffic file with
only ``rate_rps`` changed). Prints one JSON line per rate: offered and
served tokens, the TTFT median and 95th percentile, and the requests
still unserved when the window closed (a backlog that grows with the
window means the rate is above what the server sustains). The cell's
rate in its traffic file is set once from this, at about four fifths of
the knee; the benchmark's runs never sweep.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

import numpy as np  # noqa: E402

from bench import run as R  # noqa: E402
from bench import spec as spec_mod  # noqa: E402
from bench import stats  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", type=float, nargs="+", required=True)
    ap.add_argument("--seed", type=int, default=1)
    args = ap.parse_args(argv)
    cell = spec_mod.load_cell(args.workload)
    if cell.traffic["loop"] != "open":
        raise SystemExit(f"{cell.name} is not an open-loop cell")
    devs = R.require_chips(cell.chips)
    if devs is None:
        return 2
    import jax
    from bench import weights
    R.enable_cache()
    params = weights.make_params(cell.hf, args.seed)
    jax.block_until_ready(params)
    for rate in args.rates:
        tr = dict(cell.traffic,
                  arrivals=dict(cell.traffic["arrivals"], rate_rps=rate))
        c = dataclasses.replace(cell, traffic=tr)
        served, _, _ = R.serve_cell(c, params, args.seed, args.seconds,
                                     warm=rate == args.rates[0])
        w = served.window
        tt, missing = stats.ttfts(served.due, served.times, w,
                                  served.grace_end)
        late = [rid for rid in served.in_scope
                if not any(t < w for t in served.times[rid][-1:])]
        R.log(rate_rps=rate, requests=len(served.in_scope),
              offered_tok_s=sum(r.max_new for r in served.in_scope.values())
              / w,
              out_tok_s=stats.window_tokens(served.times, w) / w,
              ttft_p50_ms=1e3 * stats.percentile(tt, 50),
              ttft_p95_ms=1e3 * stats.percentile(tt, 95),
              itl_p95_ms=1e3 * stats.percentile(
                  stats.window_gaps(served.times, w), 95),
              unfinished_at_close=len(late), missing=missing,
              drain_s=served.grace_end - w,
              generator_late_max_ms=1e3 * float(np.max(served.lateness)),
              setup_phases=served.setup_phases)
    return 0


if __name__ == "__main__":
    sys.exit(main())
