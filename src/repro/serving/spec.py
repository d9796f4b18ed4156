"""Declarative engine construction (PR 10): ``EngineSpec``.

The spec is the ONE way to describe an engine — model, serving policy,
and shard layout — separated from the runtime inputs (params, latency
model) that ``build()`` takes. Frozen and hashable, so specs can key
caches and travel through cluster/CLI layers by value.

``shard > 1`` builds the engine across that many local XLA devices on a
1-D ``("model",)`` mesh: params are tensor-sharded (GSPMD,
``distributed.sharding.param_shardings``), the hot ring splits its slot
axis and the paged pool its block axis across the mesh, and the fused
decode step merges per-shard attention partials with the exact Alg. 1
``pmax``/``psum`` reduction (``distributed.pam_shard``). Token streams
are bit-identical to the unsharded engine; see
docs/ARCHITECTURE.md#shard-layout.

The legacy ``ServingEngine(cfg, params, scfg, ...)`` constructor
survives as a deprecation shim that builds an ``EngineSpec``
internally.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable, Optional, Sequence

from repro.models.config import ModelConfig
from repro.serving.engine import ServingConfig, ServingEngine


@dataclasses.dataclass(frozen=True)
class EngineSpec:
    """What an engine IS: model + serving policy + shard layout + name.

    ``build(params, latency_model=...)`` turns the spec into a running
    ``ServingEngine``; everything else about the engine derives from
    these four fields. ``validate()`` raises actionable ``ValueError``s
    for spec-level inconsistencies (shard divisibility); device
    availability is only checked at build time, so specs can be
    constructed and round-tripped on any host.
    """

    model: ModelConfig
    serving: ServingConfig = ServingConfig()
    shard: int = 1
    name: str = "dev0"

    def validate(self) -> "EngineSpec":
        s, scfg = self.shard, self.serving
        if s < 1:
            raise ValueError(f"EngineSpec.shard must be >= 1, got {s}")
        if s == 1:
            return self
        if scfg.pam is None or not scfg.block_size:
            raise ValueError(
                f"shard={s} requires the PAM paged path (pam config + "
                f"block_size > 0): the sharded decode step splits the "
                f"hot ring and the paged pool across the mesh")
        window = scfg.hot_window or scfg.max_len
        if window % s:
            raise ValueError(
                f"shard={s}: hot ring of {window} slots does not split "
                f"evenly — pick hot_window (or max_len) divisible by "
                f"{s}, e.g. hot_window={-(-window // s) * s}")
        nb = self.total_pool_blocks()
        if nb % s:
            raise ValueError(
                f"shard={s}: pool of {nb} physical blocks (pool_blocks "
                f"+ 1 sentinel) does not split evenly — pass "
                f"pool_blocks={-(-nb // s) * s - 1} instead of "
                f"{nb - 1}")
        return self

    def total_pool_blocks(self) -> int:
        """Physical pool blocks including the sentinel trash block —
        the size of the pool's (sharded) block axis. 0 when dense."""
        scfg = self.serving
        if not scfg.block_size:
            return 0
        per_seq = scfg.max_len // max(scfg.block_size, 1)
        nb = (scfg.pool_blocks if scfg.pool_blocks is not None
              else scfg.max_batch * per_seq)
        return nb + 1

    def build(self, params: Any, *,
              latency_model: Optional[Callable[[dict], float]] = None,
              devices: Optional[Sequence[Any]] = None) -> ServingEngine:
        """Materialize the engine (the canonical constructor path).

        ``devices`` — exactly ``shard`` local JAX devices — pins the
        engine's params, cache and state to them; by default a one-device
        engine uses JAX's default device and a sharded one the first
        ``shard`` devices."""
        return ServingEngine(self, params, latency_model=latency_model,
                             devices=None if devices is None
                             else tuple(devices))
