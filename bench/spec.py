"""Find a cell's files by the names in BENCHMARK.json.

A cell names a configuration (``configs/<config>.json``, the file the
BENCHMARK entry gives) and a traffic mix (``traffic/<traffic>.json``). Its
correctness limit is ``limits/<cell>.json``; its model family is
``families/<model_type>.py``. Nothing here knows any cell by name.
"""

from __future__ import annotations

import dataclasses
import json
import os

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
REPO_ROOT = os.path.dirname(BENCH_DIR)


def _load(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


@dataclasses.dataclass(frozen=True)
class Cell:
    name: str
    config_name: str
    hf: dict                # the configuration file as run
    traffic_name: str
    traffic: dict
    limits: dict
    chips: int
    run_seconds: int
    end_to_end: tuple       # metric entries of BENCHMARK.json this cell reports
    per_layer: tuple


def _applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_cell(name: str, root: str = REPO_ROOT) -> Cell:
    bench = _load(os.path.join(root, "BENCHMARK.json"))
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json")
    w = cells[name]
    configs = {c["name"]: c for c in bench["configs"]}
    c = configs[w["config"]]
    return Cell(
        name=name, config_name=c["name"],
        hf=_load(os.path.join(root, c["file"])),
        traffic_name=w["traffic"],
        traffic=_load(os.path.join(BENCH_DIR, "traffic",
                                   w["traffic"] + ".json")),
        limits=_load(os.path.join(BENCH_DIR, "limits", name + ".json")),
        chips=int(w["chips"]), run_seconds=int(bench["run_seconds"]),
        end_to_end=tuple(m for m in bench["end_to_end"] if _applies(m, name)),
        per_layer=tuple(m for m in bench["per_layer"] if _applies(m, name)))


def model_config(hf: dict, name: str):
    """The serving program's ``ModelConfig`` for a config file, from its
    family (``bench/families/<model_type>.py``)."""
    from bench import families
    return families.of(hf).model_config(hf, name)


def serving_config(traffic: dict):
    """The serving program's ``ServingConfig`` from a traffic file's
    ``serving`` geometry (PAM on, greedy sampling, no EOS)."""
    from repro.serving import PAMManagerConfig, ServingConfig
    s = traffic["serving"]
    p = s["pam"]
    pam = PAMManagerConfig(
        max_tokens=s["max_len"], hot_capacity=p["hot_capacity"],
        warm_capacity=p["warm_capacity"], compression=p["compression"],
        recency_window=p["recency_window"],
        schedule_interval=p["schedule_interval"], lam=p["lam"])
    return ServingConfig(max_batch=s["max_batch"], max_len=s["max_len"],
                         pam=pam, block_size=s["block_size"],
                         hot_window=s["hot_window"],
                         pool_blocks=s["pool_blocks"])
