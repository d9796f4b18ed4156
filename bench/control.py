#!/usr/bin/env python3
"""Readings that set a cell's correctness limit, on the chip.

    python bench/control.py --workload <cell> --seconds <s> --seeds 11 12 ...

For each seed, in one process: the cell's weights and traffic from the
seed, a window of ``--seconds`` through the served path, and then the
check's numbers (the widest and the mean logit gap over the positions
the check compares) for the tokens the program served (the lower
readings) and for the tokens each control, the reference in int8 or fp8
(weights, activations and K/V), puts first at the same positions (the
upper readings). Prints one JSON line per seed and a summary. The
benchmark's own runs never run a control.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys


ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
from bench import run as R  # noqa: E402
from bench import spec as spec_mod  # noqa: E402

NUMBERS = ("max_logit_gap", "mean_logit_gap")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--controls", nargs="+", default=["int8", "fp8"],
                    choices=["int8", "fp8"])
    ap.add_argument("--positions", type=int, default=None,
                    help="served tokens compared per request (default: "
                         "the cell's limit file)")
    args = ap.parse_args(argv)
    cell = spec_mod.load_cell(args.workload)
    if args.positions:
        cell = dataclasses.replace(cell, limits=dict(
            cell.limits, positions=args.positions))
    devs = R.require_chips(cell.chips)
    if devs is None:
        return 2
    import jax
    R.enable_cache()
    rows = []
    for seed in args.seeds:
        params = R.make_weights(cell, seed)
        jax.block_until_ready(params)
        served, _, _ = R.serve_cell(cell, params, seed, args.seconds,
                                     warm=seed == args.seeds[0])
        del params
        if not any(served.tokens.values()):
            R.log(seed=seed, finished=0, in_scope=len(served.in_scope),
                  setup_phases=served.setup_phases)
            continue
        checks, extra = R.check(
            cell, lambda s=seed: R.make_weights(cell, s), served,
            seed, controls=tuple(args.controls))
        row = {"seed": seed,
               "program": {n: checks[n]["value"] for n in NUMBERS},
               "controls": extra["controls"],
               "compared": extra["compared"],
               "checked_tokens": extra["checked_tokens"],
               "reference_s": extra["reference_s"],
               "finished": len(served.finished),
               "in_scope": len(served.in_scope),
               "setup_phases": served.setup_phases}
        rows.append(row)
        R.log(**row, by_position=extra["by_position"],
              control_by_position=extra["control_by_position"])
        del served
    summary = {"workload": cell.name, "seeds": args.seeds}
    for n in NUMBERS:
        summary[n] = {"lower_reading": max(
            (r["program"][n] for r in rows), default=None)}
        for c in args.controls:
            summary[n][c + "_upper_reading"] = min(
                (r["controls"][c][n] for r in rows), default=None)
    print(json.dumps(summary), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
