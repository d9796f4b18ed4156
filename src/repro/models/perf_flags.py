"""Trace-time performance switches for §Perf hillclimbing.

Each flag is a beyond-paper optimization toggled per dry-run variant so
before/after lowered artifacts can be compared cell-by-cell:

  sp_pin      pin sequence-parallel sharding on intra-block activations
              (attention/MLP inputs + outputs) — shrinks TP psum traffic
              from full activations to S-sharded activations
  bf16_probs  cast softmax probabilities to bf16 for the PV matmul —
              halves the dominant score-materialization bytes
  remat_dots  remat policy saves matmul outputs (no matmul recompute in
              the backward re-forward)
  pam_shard_decode  decode attention + cache update fused in one shard_map
              over the sequence axis (PAMattention distributed form) —
              removes the gather the GSPMD cache-scatter inserts
"""

from __future__ import annotations

import os

_FLAGS: set[str] = set()


def set_flags(*names: str) -> None:
    _FLAGS.clear()
    _FLAGS.update(names)


def from_env() -> None:
    set_flags(*[f for f in os.environ.get("REPRO_PERF", "").split(",") if f])


def enabled(name: str) -> bool:
    return name in _FLAGS


def active() -> tuple[str, ...]:
    return tuple(sorted(_FLAGS))
