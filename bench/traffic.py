"""Seeded request generator driven by a traffic file (``traffic/<name>.json``).

Every seed gets the same multiset of sizes and gaps, in another order:
requests are drawn in strata of ``stratum`` requests, and each stratum
holds the distribution's quantiles at ``(i + 1/2) / stratum``, shuffled by
the seed. So any run that consumes whole strata does the same work, and
runs of different seeds differ by order and token ids only. A traffic
file that sets ``schedule_seed`` shuffles by that seed instead: every
run then sends the same sizes in the same order, and the run's seed
draws only the token ids. A closed loop needs it where the order decides
the work in the window (which clients wait for pool blocks, how many
answers end inside it).

Arrival shapes follow ``repro.frontend.loadgen._arrival_times`` (poisson
and gamma renewal gaps), with lognormal lengths and a closed loop added.
"""

from __future__ import annotations

import dataclasses
import math
import statistics

import numpy as np


@dataclasses.dataclass(frozen=True)
class Req:
    id: int
    prompt: np.ndarray          # (P,) int32
    max_new: int
    due: float                  # seconds after the window opens (open loop)


def _quantiles(n: int) -> np.ndarray:
    return (np.arange(n) + 0.5) / n


def length_quantiles(spec: dict, qs: np.ndarray) -> np.ndarray:
    """Integer lengths at quantiles ``qs`` of ``spec`` (``uniform`` over
    ``[lo, hi]``, or ``lognormal`` with ``median`` and ``sigma``, clipped
    to ``[lo, hi]``)."""
    lo, hi = int(spec["lo"]), int(spec["hi"])
    if spec["dist"] == "uniform":
        vals = lo + np.floor(qs * (hi - lo + 1))
    elif spec["dist"] == "lognormal":
        nd = statistics.NormalDist()
        z = np.array([nd.inv_cdf(float(q)) for q in qs])
        vals = np.rint(np.exp(math.log(spec["median"]) + spec["sigma"] * z))
    else:
        raise ValueError(f"unknown length distribution {spec['dist']!r}")
    return np.clip(vals, lo, hi).astype(np.int64)


def gap_quantiles(spec: dict, qs: np.ndarray) -> np.ndarray:
    """Inter-arrival gaps (s) at quantiles ``qs``: ``poisson`` (exponential
    gaps at ``rate_rps``) or ``gamma`` (mean ``1/rate_rps``, squared
    coefficient of variation ``cv2``)."""
    rate = float(spec["rate_rps"])
    if rate <= 0:
        raise ValueError(f"rate_rps must be positive, got {rate}")
    if spec["kind"] == "poisson":
        return -np.log1p(-qs) / rate
    if spec["kind"] == "gamma":
        from scipy.stats import gamma
        cv2 = float(spec["cv2"])
        return gamma.ppf(qs, a=1.0 / cv2, scale=cv2 / rate)
    raise ValueError(f"unknown arrival kind {spec['kind']!r}")


def _stratified(values_of, n: int, stratum: int, rng) -> np.ndarray:
    base = values_of(_quantiles(stratum))
    out = [rng.permutation(base) for _ in range(-(-n // stratum))]
    return np.concatenate(out)[:n]


def make_requests(traffic: dict, *, seed: int, vocab: int, max_len: int,
                  seconds: float, first_id: int = 0) -> list[Req]:
    """The run's requests in the order they are sent.

    Open loop: requests due in ``[0, seconds)``, each with its due time.
    Closed loop: ``traffic["requests"]`` requests (``due`` 0), taken in
    order by whichever client is free. Answers are cut so that prompt
    plus answer fits ``max_len``."""
    rng = np.random.default_rng(seed)
    order = (np.random.default_rng(int(traffic["schedule_seed"]))
             if "schedule_seed" in traffic else rng)
    stratum = int(traffic.get("stratum", 16))
    if traffic["loop"] == "open":
        rate = float(traffic["arrivals"]["rate_rps"])
        n = max(int(math.ceil(rate * seconds)), 1)
        gaps = _stratified(lambda q: gap_quantiles(traffic["arrivals"], q),
                           n, stratum, order)
        due = np.cumsum(gaps) - gaps[0]
        n = int(np.sum(due < seconds))
        due = due[:n]
    elif traffic["loop"] == "closed":
        n = int(traffic["requests"])
        due = np.zeros(n)
    else:
        raise ValueError(f"unknown loop {traffic['loop']!r}")
    prompts = _stratified(lambda q: length_quantiles(traffic["prompt"], q),
                          n, stratum, order)
    answers = _stratified(lambda q: length_quantiles(traffic["answer"], q),
                          n, stratum, order)
    reqs = []
    for i in range(n):
        plen = int(prompts[i])
        if plen >= max_len:
            raise ValueError(f"prompt of {plen} tokens does not fit "
                             f"max_len {max_len}")
        new = int(min(answers[i], max_len - plen))
        toks = rng.integers(0, vocab, plen, dtype=np.int64).astype(np.int32)
        reqs.append(Req(id=first_id + i, prompt=toks, max_new=new,
                        due=float(due[i])))
    return reqs
