"""Distributed PAMattention (paper Alg. 1 across devices) via shard_map.

Layout: KV caches sequence-sharded on the ``model`` mesh axis — each device
plays the role of one PIM site holding its KV partition. One decode step:

  local stage   : each device attends its own KV shard -> (O, m, l)
  merge stage   : exact online-softmax reduction across the axis —
                  m* = pmax(m);  O = psum(e^{m-m*} O);  l = psum(e^{m-m*} l)

The merge communicates H x (d + 2) floats per device — independent of
context length. A gather-based scheme would move the whole KV shard
(S_local x H_kv x d); this is the paper's "reduce communication" claim,
and the collective-bytes delta shows up directly in the dry-run roofline.

``sequence_sharded_decode_attn`` plugs straight into
``transformer.decode_step(decode_attn_fn=...)``.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P


@functools.lru_cache(maxsize=None)
def decode_mesh(devices: tuple, *, axis: str = "model") -> Mesh:
    """The serving engine's 1-D decode mesh over ``devices`` (a tuple of
    local JAX devices). Cached so every engine on the same devices
    shares ONE mesh object — which is what lets their jitted dispatches
    share the module-level compile caches."""
    return Mesh(np.asarray(devices), (axis,))


def merge_collective_bytes(n_layers: int, n_heads: int, head_dim: int,
                           batch: int, *, smax: int = 0
                           ) -> tuple[int, int]:
    """Modeled per-device collective bytes of ONE sharded decode step.

    Returns ``(merge_bytes, mass_bytes)``: ``merge_bytes`` is the Alg. 1
    cross-shard reduction — ``pmax``/``psum`` of the ``(O, m, l)``
    triple, i.e. ``H x (d + 2)`` fp32 per layer per batch row —
    independent of context length (the paper's flat-communication
    claim). ``mass_bytes`` is the importance-mass psum that keeps the
    EMA/Alg. 2 state replicated — an observability side channel that IS
    linear in ``smax`` and is reported separately in benchmarks."""
    merge = n_layers * batch * n_heads * (head_dim + 2) * 4
    mass = n_layers * batch * smax * 4
    return merge, mass


def make_sequence_sharded_decode_attn(mesh: Mesh, *, axis: str = "model",
                                      dp=None):
    """Returns a decode_attn_fn (q, k_cache, v_cache, kv_lens) -> (out,
    mass) computing PAMattention with KV sequence-sharded over ``axis``.

    q: (B, H, dh) replicated over ``axis``; caches (B, Hkv, S, dh) sharded
    on S; kv_lens (B,). ``mass`` is returned sequence-sharded-consistent
    (global (B, S) array, sharded like the cache on its S axis).
    """

    def local_fn(q, k, v, kv_lens):
        # shapes here are PER-SHARD: k/v (B, Hkv, S_loc, dh)
        B, H, dh = q.shape
        Hkv, S_loc = k.shape[1], k.shape[2]
        rep = H // Hkv
        scale = 1.0 / math.sqrt(dh)
        shard = jax.lax.axis_index(axis)
        start = shard * S_loc
        pos = start + jnp.arange(S_loc)                    # global positions
        live = pos[None, :] < kv_lens[:, None]             # (B, S_loc)

        # grouped (GQA) form: NO jnp.repeat KV expansion — query heads are
        # contracted against their shared kv head directly
        qg = q.reshape(B, Hkv, rep, dh)
        s = jnp.einsum("bgrd,bgsd->bgrs", qg.astype(jnp.float32),
                       k.astype(jnp.float32)) * scale
        s = jnp.where(live[:, None, None, :], s, -jnp.inf)

        # ---- local partial (Alg. 1 Local_Attention) ----------------------
        m_loc = jnp.max(s, axis=-1)                        # (B, Hkv, rep)
        m_safe = jnp.where(jnp.isfinite(m_loc), m_loc, 0.0)
        p = jnp.exp(s - m_safe[..., None])
        p = jnp.where(live[:, None, None, :], p, 0.0)
        l_loc = jnp.sum(p, axis=-1)
        o_loc = jnp.einsum("bgrs,bgsd->bgrd", p, v.astype(jnp.float32))

        # ---- inter-device reduction (Alg. 1 Reduction) --------------------
        m_star = jax.lax.pmax(m_loc, axis)
        m_star_safe = jnp.where(jnp.isfinite(m_star), m_star, 0.0)
        w = jnp.where(jnp.isfinite(m_loc),
                      jnp.exp(m_loc - m_star_safe), 0.0)   # (B, Hkv, rep)
        o = jax.lax.psum(w[..., None] * o_loc, axis)
        l = jax.lax.psum(w * l_loc, axis)
        l_safe = jnp.where(l > 0, l, 1.0)
        out = (o / l_safe[..., None]).reshape(B, H, dh).astype(q.dtype)

        # per-token mass on MY shard, normalized by the global (m*, l)
        p_norm = (p * w[..., None]) / l_safe[..., None]
        n_live = jax.lax.psum(jnp.sum(live, axis=-1), axis)  # (B,)
        mass = (jnp.mean(p_norm, axis=(1, 2))
                * n_live[:, None].astype(jnp.float32))
        return out, mass

    return jax.shard_map(
        local_fn, mesh=mesh,
        in_specs=(P(dp), P(dp, None, axis, None), P(dp, None, axis, None),
                  P(dp)),
        out_specs=(P(dp), P(dp, axis)),
        check_vma=False,
    )


def fused_update_decode(q, k_cache, v_cache, k_new, v_new, kv_lens, *,
                        axis: str = "model"):
    """§Perf ``pam_shard_decode``: one shard_map doing BOTH the new-token
    cache write and PAMattention over the sequence-sharded cache.

    The baseline lets GSPMD lower ``cache.at[b, :, pos].set(new)`` on a
    sequence-sharded axis, which materializes a gather of the whole cache;
    here each shard applies the write only if ``pos`` falls in its range
    (a masked local dynamic-update), then computes its local partial and
    joins the exact psum merge. Uses the ambient abstract mesh.

    q: (B, H, dh); caches (B, Hkv, S, dh) sequence-sharded on ``axis``;
    k_new/v_new: (B, Hkv, dh); kv_lens: (B,) pre-append lengths.
    Returns (out, mass, k_cache, v_cache).
    """
    mesh = jax.sharding.get_abstract_mesh()
    B = q.shape[0]
    dp: tuple | None = tuple(a for a in mesh.axis_names
                             if a in ("pod", "data")) or None
    if dp is not None:
        dp_size = 1
        for a in dp:
            dp_size *= mesh.shape[a]
        if B % dp_size:
            dp = None

    def local(q, kc, vc, kn, vn, lens):
        Bl, H, dh = q.shape
        Hkv, S_loc = kc.shape[1], kc.shape[2]
        rep = H // Hkv
        scale = 1.0 / math.sqrt(dh)
        shard = jax.lax.axis_index(axis)
        start = shard * S_loc

        # ---- masked local cache write (the paper's intra-device mapping:
        # the owning bank group takes the token; everyone else no-ops) ----
        pos_local = lens - start
        in_range = (pos_local >= 0) & (pos_local < S_loc)
        safe = jnp.clip(pos_local, 0, S_loc - 1)
        bidx = jnp.arange(Bl)
        old_k = kc[bidx, :, safe]
        old_v = vc[bidx, :, safe]
        kc = kc.at[bidx, :, safe].set(
            jnp.where(in_range[:, None, None], kn, old_k))
        vc = vc.at[bidx, :, safe].set(
            jnp.where(in_range[:, None, None], vn, old_v))

        # ---- local partial + exact psum merge (Alg. 1) -------------------
        # grouped (GQA) form: NO jnp.repeat — the baseline materializes
        # rep x the KV shard; here queries are grouped per kv head instead
        live = (start + jnp.arange(S_loc))[None, :] < (lens + 1)[:, None]
        qg = q.reshape(Bl, Hkv, rep, dh)
        # bf16 operands read directly, fp32 accumulate: no cast copy of the
        # KV shard (iteration 3 of §Perf cell A)
        s = jnp.einsum("bgrd,bgsd->bgrs", qg, kc,
                       preferred_element_type=jnp.float32) * scale
        s = jnp.where(live[:, None, None, :], s, -jnp.inf)
        m_loc = jnp.max(s, axis=-1)                        # (B, Hkv, rep)
        m_safe = jnp.where(jnp.isfinite(m_loc), m_loc, 0.0)
        p = jnp.exp(s - m_safe[..., None])
        p = jnp.where(live[:, None, None, :], p, 0.0)
        l_loc = jnp.sum(p, axis=-1)
        o_loc = jnp.einsum("bgrs,bgsd->bgrd", p, vc,
                           preferred_element_type=jnp.float32)

        m_star = jax.lax.pmax(m_loc, axis)
        m_star_safe = jnp.where(jnp.isfinite(m_star), m_star, 0.0)
        w = jnp.where(jnp.isfinite(m_loc),
                      jnp.exp(m_loc - m_star_safe), 0.0)
        o = jax.lax.psum(w[..., None] * o_loc, axis)
        l = jax.lax.psum(w * l_loc, axis)
        l_safe = jnp.where(l > 0, l, 1.0)
        out = (o / l_safe[..., None]).reshape(Bl, H, dh).astype(q.dtype)

        p_norm = (p * w[..., None]) / l_safe[..., None]    # (B,Hkv,rep,S)
        n_live = jax.lax.psum(jnp.sum(live, axis=-1), axis)
        mass = (jnp.mean(p_norm, axis=(1, 2))
                * n_live[:, None].astype(jnp.float32))
        return out, mass, kc, vc

    kv_spec = P(dp, None, axis, None)
    return jax.shard_map(
        local, mesh=mesh,
        in_specs=(P(dp), kv_spec, kv_spec, P(dp), P(dp), P(dp)),
        out_specs=(P(dp), P(dp, axis), kv_spec, kv_spec),
        check_vma=False,
    )(q, k_cache, v_cache, k_new, v_new, kv_lens)


def make_sharded_paged_decode_attn(mesh: Mesh, hot_mask, paged_mask,
                                   block_table, *,
                                   axis: str = "model", scale=None):
    """The PR 10 tentpole attention: hot-ring ⊕ paged partials with the
    ring's SLOT axis and the pool's BLOCK axis sharded over ``axis``.

    Drop-in twin of ``pam_manager.make_paged_decode_attn`` — returns a
    ``decode_attn_fn(q, k_cache, v_cache, pk, pv, kv_lens) -> (out,
    mass)`` for ``transformer.decode_step`` — but the per-layer ring
    ``(B, Hkv, W, dh)`` is split on W and the per-layer pool
    ``(NB+1, bs, Hkv, dh)`` on its physical-block axis. Each shard:

      * owns ring slots ``[r·W_loc, (r+1)·W_loc)`` — its slice of the
        rotated position map (``ring_position_map(start=...)``) maps
        them to absolute positions, and since an in-window position
        lives in exactly one global slot, hot contributions PARTITION
        across shards;
      * owns physical blocks ``[r·NB_loc, (r+1)·NB_loc)`` — the GLOBAL
        block table is an explicit replicated operand (tables survive
        distribution unchanged, the PagedAttention property) and
        non-local entries are masked to the merge identity
        (``ops.paged_decode_attention_partial(block_offset=...)``,
        Pallas table-walk on TPU, jnp gather elsewhere);
      * merges its hot+paged partials locally (exact Alg. 1), then
        joins the cross-shard ``pmax``/``psum`` of ``(O, m, l)`` —
        ``H x (d+2)`` fp32 per device, independent of context length.

    ``out`` and ``mass`` come back REPLICATED (the mass is psum-merged
    onto absolute coordinates), so the importance-EMA/Alg. 2 state and
    the sampling path downstream are untouched by sharding — which is
    why sharded token streams are bit-exact twins of unsharded ones.

    The masks/table are traced per-step values, and shard_map forbids
    closing over traced arrays — they ride as explicit replicated
    operands instead.
    """
    from repro.core import online_softmax as osm
    from repro.core.pam_interface import paged_gather_logical
    from repro.kernels import ops
    from repro.kernels.flash_decode import (ring_gather_mask,
                                            ring_position_map)
    nshards = mesh.shape[axis]

    def local_fn(q, kc, vc, pk, pv, bt, hot_mask, paged_mask, kv_lens):
        B, H, d = q.shape
        Hkv, W_loc = kc.shape[1], kc.shape[2]
        NB_loc, bs = pk.shape[0], pk.shape[1]
        Smax = hot_mask.shape[1]
        rep = H // Hkv
        sc = scale if scale is not None else 1.0 / (d ** 0.5)
        r = jax.lax.axis_index(axis)
        live_len = jnp.arange(Smax)[None, :] < kv_lens[:, None]
        hot = hot_mask & live_len
        pgd = paged_mask & live_len

        # ---- hot partial over MY ring slots ---------------------------
        ring_pos, ring_valid = ring_position_map(
            kv_lens, W_loc * nshards, start=r * W_loc, size=W_loc)
        hot_ring = ring_gather_mask(hot, ring_pos, ring_valid)
        s_ring = ops._grouped_scores(q, kc, sc)     # (B, Hkv, rep, W_loc)
        part = ops._grouped_partial_from_scores(s_ring, vc, hot_ring)

        # ---- paged partial over MY physical blocks --------------------
        lo = r * NB_loc
        part_pgd = ops.paged_decode_attention_partial(
            q, pk, pv, bt, pgd, block_offset=lo, scale=sc)
        merged = osm.merge_partials(part, part_pgd)

        # ---- cross-shard reduction (Alg. 1 across devices) ------------
        m_loc, l_loc, o_loc = merged.m, merged.l, merged.o
        m_star = jax.lax.pmax(m_loc, axis)
        m_star_safe = jnp.where(jnp.isfinite(m_star), m_star, 0.0)
        w = jnp.where(jnp.isfinite(m_loc),
                      jnp.exp(m_loc - m_star_safe), 0.0)     # (B, H)
        o = jax.lax.psum(w[..., None] * o_loc, axis)
        l = jax.lax.psum(w * l_loc, axis)
        inv_l = 1.0 / jnp.maximum(l, 1e-30)
        out = (o * inv_l[..., None]).astype(q.dtype)

        # ---- union mass on absolute coordinates, from global (m*, l) --
        mg = m_star_safe.reshape(B, Hkv, rep)
        il = inv_l.reshape(B, Hkv, rep)[..., None]
        inside = (bt >= lo) & (bt < lo + NB_loc)
        pgd_loc = pgd & jnp.repeat(inside, bs, axis=1)
        bt_loc = jnp.where(inside, bt - lo, 0)
        gk = paged_gather_logical(pk, bt_loc)       # (B, Hkv, Smax, d)
        s_pool = ops._grouped_scores(q, gk, sc)

        def probs(s, mask):
            s = jnp.where(mask[:, None, None, :], s, -jnp.inf)
            p = jnp.exp(s - mg[..., None]) * il
            return jnp.where(jnp.isfinite(s), p, 0.0)

        ph = jnp.mean(probs(s_ring, hot_ring), axis=(1, 2))  # (B, W_loc)
        pp = jnp.mean(probs(s_pool, pgd_loc), axis=(1, 2))   # (B, Smax)
        bidx = jnp.arange(B)[:, None]
        scatter_idx = jnp.clip(ring_pos, 0, Smax - 1)
        mass = jax.lax.psum(
            pp.at[bidx, scatter_idx].add(jnp.where(hot_ring, ph, 0.0)),
            axis)
        hot_eff = jax.lax.pmax(
            jnp.zeros((B, Smax), jnp.int32).at[bidx, scatter_idx].max(
                hot_ring.astype(jnp.int32)), axis).astype(bool)
        n_live = jnp.sum(hot_eff | pgd, axis=-1,
                         keepdims=True).astype(jnp.float32)
        return out, mass * n_live

    sharded = jax.shard_map(
        local_fn, mesh=mesh,
        in_specs=(P(), P(None, None, axis, None), P(None, None, axis, None),
                  P(axis), P(axis), P(), P(), P(), P()),
        out_specs=(P(), P()),
        check_vma=False,
    )

    def decode_attn_fn(q, k_cache, v_cache, pk, pv, kv_lens):
        return sharded(q, k_cache, v_cache, pk, pv, block_table,
                       hot_mask, paged_mask, kv_lens)

    return decode_attn_fn


def make_gather_based_decode_attn(mesh: Mesh, *, axis: str = "model",
                                  dp=None):
    """The L-PIM / request-level baseline (paper §3.3.1 C1): all-gather the
    KV shards to every device, then attend locally. Same numerics, O(S)
    collective bytes — kept as the ablation/benchmark counterpart."""

    def local_fn(q, k, v, kv_lens):
        k_full = jax.lax.all_gather(k, axis, axis=2, tiled=True)
        v_full = jax.lax.all_gather(v, axis, axis=2, tiled=True)
        from repro.models.attention import dense_decode_attn
        return dense_decode_attn(q, k_full, v_full, kv_lens)

    return jax.shard_map(
        local_fn, mesh=mesh,
        in_specs=(P(dp), P(dp, None, axis, None), P(dp, None, axis, None),
                  P(dp)),
        out_specs=(P(dp), P(dp, None)),
        check_vma=False,
    )
