"""GPipe-style pipeline parallelism via shard_map + ppermute.

Layers are grouped into S stages stacked on a ``stage`` mesh axis; M
microbatches stream through with the classic (M + S - 1)-tick schedule.
Each tick every device applies its stage to its current activation and
ppermutes it to the next stage — compute on tick t overlaps the transfer
issued on tick t-1 (the overlap trick the launcher exposes for deep models
like deepseek-67b where pure TP over 16 devices under-utilizes).

This module is self-contained (used by tests and the scalability
benchmark); the dry-run meshes use DP x TP + sequence-sharded PAMattention,
with PP offered as a launcher option — see DESIGN.md §6.
"""

from __future__ import annotations

from typing import Callable

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P


def pipeline_apply(mesh: Mesh, stage_fn: Callable, n_stages: int,
                   axis: str = "stage"):
    """Build a pipelined apply.

    stage_fn(stage_params, x) -> x : applies ONE stage's layers.
    Returns f(stacked_params, x_microbatched) where stacked_params has a
    leading (n_stages,) axis sharded on ``axis`` and x_microbatched is
    (M, mb, ...) replicated. Output matches x_microbatched.
    """

    def pipelined(stage_params, xs):
        # the stage axis is sharded to size 1 per device — strip it
        stage_params = jax.tree.map(lambda x: x[0], stage_params)
        M = xs.shape[0]
        ticks = M + n_stages - 1
        my_stage = jax.lax.axis_index(axis)
        perm = [(i, (i + 1) % n_stages) for i in range(n_stages)]

        state = jnp.zeros_like(xs[0])            # activation in flight
        outputs = jnp.zeros_like(xs)

        def tick(carry, t):
            state, outputs = carry
            # stage 0 ingests microbatch t (when available)
            mb_idx = jnp.clip(t, 0, M - 1)
            fresh = xs[mb_idx]
            inp = jnp.where(my_stage == 0, fresh, state)
            out = stage_fn(stage_params, inp)
            # last stage emits microbatch (t - (S-1))
            emit_idx = jnp.clip(t - (n_stages - 1), 0, M - 1)
            valid_emit = (t >= n_stages - 1) & (my_stage == n_stages - 1)
            outputs = jax.lax.cond(
                valid_emit,
                lambda o: o.at[emit_idx].set(out),
                lambda o: o, outputs)
            # rotate activations stage i -> i+1
            state = jax.lax.ppermute(out, axis, perm)
            return (state, outputs), None

        (state, outputs), _ = jax.lax.scan(
            tick, (state, outputs), jnp.arange(ticks))
        # outputs live on the last stage; broadcast to all for the caller
        outputs = jax.lax.psum(
            jnp.where(my_stage == n_stages - 1, outputs, 0.0), axis)
        return outputs

    def run(stacked_params, xs):
        pp = jax.tree.map(lambda _: P(axis), stacked_params)
        return jax.shard_map(
            pipelined, mesh=mesh,
            in_specs=(pp, P()),
            out_specs=P(),
            check_vma=False,
        )(stacked_params, xs)

    return run


def stages_from_layers(layer_params, n_stages: int):
    """Regroup scan-stacked per-layer params (L, ...) into
    (n_stages, L//n_stages, ...)."""
    def regroup(x):
        L = x.shape[0]
        assert L % n_stages == 0, (L, n_stages)
        return x.reshape((n_stages, L // n_stages) + x.shape[1:])
    return jax.tree.map(regroup, layer_params)
