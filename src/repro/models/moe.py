"""Routed top-k MoE with shared experts (Qwen3-MoE / DeepSeek-V2 style).

Sort-based capacity dispatch (MegaBlocks-style, dense-shape form):
tokens are ranked per expert, gathered into an (E, C, d) batch, processed
with one batched matmul per projection, and combined by gate weight.
Expert-parallel sharding shards the leading E axis of both the expert
weights and the (E, C, d) dispatch buffers over the `model` mesh axis —
XLA inserts the all-to-all pair.
"""

from __future__ import annotations

import math
from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.models.config import MoEConfig
from repro.models.layers import init_linear


class MoEParams(NamedTuple):
    router: jax.Array       # (d, E)
    w_gate: jax.Array       # (E, d, f)
    w_up: jax.Array         # (E, d, f)
    w_down: jax.Array       # (E, f, d)
    shared_gate: jax.Array | None   # (d, n_shared*f) fused shared experts
    shared_up: jax.Array | None
    shared_down: jax.Array | None


def init_moe(key, d: int, cfg: MoEConfig, dtype) -> MoEParams:
    ks = jax.random.split(key, 7)
    E, f = cfg.num_experts, cfg.d_expert
    scale = 1.0 / math.sqrt(d)
    w_gate = (jax.random.normal(ks[1], (E, d, f), jnp.float32) * scale).astype(dtype)
    w_up = (jax.random.normal(ks[2], (E, d, f), jnp.float32) * scale).astype(dtype)
    w_down = (jax.random.normal(ks[3], (E, f, d), jnp.float32)
              / math.sqrt(f)).astype(dtype)
    sh = cfg.num_shared
    return MoEParams(
        router=init_linear(ks[0], d, E, jnp.float32),
        w_gate=w_gate, w_up=w_up, w_down=w_down,
        shared_gate=init_linear(ks[4], d, sh * f, dtype) if sh else None,
        shared_up=init_linear(ks[5], d, sh * f, dtype) if sh else None,
        shared_down=init_linear(ks[6], sh * f, d, dtype) if sh else None,
    )


def moe_forward(p: MoEParams, x: jax.Array, cfg: MoEConfig
                ) -> tuple[jax.Array, jax.Array]:
    """x: (B, S, d) -> (y (B, S, d), aux_loss scalar)."""
    B, S, d = x.shape
    T = B * S
    E, K = cfg.num_experts, cfg.top_k
    xf = x.reshape(T, d)

    logits = jnp.einsum("td,de->te", xf.astype(jnp.float32), p.router)
    probs = jax.nn.softmax(logits, axis=-1)                     # (T, E)
    gate_vals, expert_ids = jax.lax.top_k(probs, K)             # (T, K)
    gate_vals = gate_vals / jnp.maximum(
        jnp.sum(gate_vals, axis=-1, keepdims=True), 1e-9)

    # load-balance auxiliary loss (Switch-style)
    me = jnp.mean(probs, axis=0)                                # (E,)
    ce = jnp.mean(
        jnp.sum(jax.nn.one_hot(expert_ids, E, dtype=jnp.float32), axis=1),
        axis=0)
    aux = cfg.router_aux_weight * E * jnp.sum(me * ce)

    # ---- sort-based dispatch with capacity ------------------------------
    cap = int(math.ceil(T * K / E * cfg.capacity_factor))
    flat_expert = expert_ids.reshape(T * K)                     # (TK,)
    flat_gate = gate_vals.reshape(T * K)
    flat_token = jnp.repeat(jnp.arange(T), K)

    # position of each assignment within its expert (stable by token order)
    onehot = jax.nn.one_hot(flat_expert, E, dtype=jnp.int32)    # (TK, E)
    pos_in_expert = (jnp.cumsum(onehot, axis=0) - onehot)
    pos_in_expert = jnp.take_along_axis(
        pos_in_expert, flat_expert[:, None], axis=1)[:, 0]      # (TK,)
    keep = pos_in_expert < cap
    slot = flat_expert * cap + pos_in_expert                    # (TK,) in [0, E*cap)
    slot = jnp.where(keep, slot, E * cap)                       # overflow -> sentinel

    # scatter token ids & gates into (E*cap,) dispatch table
    tok_table = jnp.full((E * cap + 1,), 0, jnp.int32).at[slot].set(
        flat_token.astype(jnp.int32))
    gate_table = jnp.zeros((E * cap + 1,), jnp.float32).at[slot].set(
        jnp.where(keep, flat_gate, 0.0))
    tok_table, gate_table = tok_table[:-1], gate_table[:-1]

    def _pin(t, spec):
        """§Perf ``moe_pin``: explicit expert-parallel constraints on the
        dispatch intermediates — without them GSPMD replicates the expert
        compute (measured ~200x the sharded ideal on the 235B MoE)."""
        from repro.models import perf_flags
        if not perf_flags.enabled("moe_pin"):
            return t
        import jax.sharding as jsh
        mesh = jax.sharding.get_abstract_mesh()
        if not ("data" in mesh.axis_names and "model" in mesh.axis_names):
            return t
        ok = all(ax is None or t.shape[i] % mesh.shape[ax] == 0
                 for i, ax in enumerate(spec))
        return jax.lax.with_sharding_constraint(
            t, jsh.PartitionSpec(*spec)) if ok else t

    # §Perf cell C verdict: neither E-axis nor capacity-axis pins localize
    # the expert matmuls under GSPMD (see EXPERIMENTS.md §Perf — the
    # capacity-axis attempt made bytes 4x and collectives 7.6x WORSE);
    # gather-based dispatch needs explicit shard_map EP all_to_all.
    xe = xf[tok_table].reshape(E, cap, d)                       # (E, C, d)
    xe = _pin(xe, ("data", None, None))
    g = _pin(jnp.einsum("ecd,edf->ecf", xe, p.w_gate),
             ("data", None, "model"))
    u = _pin(jnp.einsum("ecd,edf->ecf", xe, p.w_up),
             ("data", None, "model"))
    ye = _pin(jnp.einsum("ecf,efd->ecd", jax.nn.silu(g) * u, p.w_down),
              ("data", None, None))

    gates = gate_table.reshape(E, cap).astype(ye.dtype)
    y = jnp.zeros((T, d), ye.dtype).at[tok_table.reshape(E * cap)].add(
        (ye * gates[..., None]).reshape(E * cap, d))

    if p.shared_gate is not None:
        sg = jnp.einsum("td,df->tf", xf, p.shared_gate)
        su = jnp.einsum("td,df->tf", xf, p.shared_up)
        y = y + jnp.einsum("tf,fd->td", jax.nn.silu(sg) * su, p.shared_down)

    return y.reshape(B, S, d).astype(x.dtype), aux


def moe_forward_dense_oracle(p: MoEParams, x: jax.Array, cfg: MoEConfig
                             ) -> jax.Array:
    """No-capacity-drop oracle (every token reaches its experts) — used by
    tests to bound the dispatch path's drop error."""
    B, S, d = x.shape
    xf = x.reshape(-1, d)
    logits = jnp.einsum("td,de->te", xf.astype(jnp.float32), p.router)
    probs = jax.nn.softmax(logits, axis=-1)
    gate_vals, expert_ids = jax.lax.top_k(probs, cfg.top_k)
    gate_vals = gate_vals / jnp.maximum(
        jnp.sum(gate_vals, axis=-1, keepdims=True), 1e-9)
    y = jnp.zeros_like(xf)
    for e in range(cfg.num_experts):
        sel = (expert_ids == e)                                  # (T, K)
        w = jnp.sum(jnp.where(sel, gate_vals, 0.0), axis=-1)     # (T,)
        g = jnp.einsum("td,df->tf", xf, p.w_gate[e])
        u = jnp.einsum("td,df->tf", xf, p.w_up[e])
        ye = jnp.einsum("tf,fd->td", jax.nn.silu(g) * u, p.w_down[e])
        y = y + w[:, None].astype(ye.dtype) * ye
    if p.shared_gate is not None:
        sg = jnp.einsum("td,df->tf", xf, p.shared_gate)
        su = jnp.einsum("td,df->tf", xf, p.shared_up)
        y = y + jnp.einsum("tf,fd->td", jax.nn.silu(sg) * su, p.shared_down)
    return y.reshape(B, S, d).astype(x.dtype)
