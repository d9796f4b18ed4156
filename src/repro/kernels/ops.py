"""Jit'd public wrappers around the Pallas kernels.

``pam_decode_attention`` is the full Alg. 1 pipeline: per-tier local stage
(flash_decode kernel over that tier's pool) followed by the hierarchical
reduction — intra-device merge over splits, inter-tier merge over tiers.
On the TPU every kernel compiles with Mosaic; interpret mode is never
chosen there. Off the TPU the serving entry points take their jnp reference
branch, and a caller that forces a kernel (``use_kernel=True``, as the
kernel tests do) gets it in interpret mode.
"""

from __future__ import annotations

import functools
from typing import Sequence

import jax
import jax.numpy as jnp

from repro.core import online_softmax as osm
from repro.kernels.flash_attention import flash_attention as _flash_attention
from repro.kernels.flash_decode import flash_decode as _flash_decode
from repro.kernels.flash_decode import flash_decode_paged as _flash_decode_paged
from repro.kernels.ssd_scan import ssd_scan as _ssd_scan


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


@functools.partial(jax.jit, static_argnames=("causal", "scale", "block_q",
                                             "block_k", "interpret"))
def fused_attention(q, k, v, *, causal=True, scale=None, block_q=128,
                    block_k=128, interpret=None):
    """Prefill/train attention. q:(B,H,S,d), k/v:(B,H_kv,S,d) -> (B,H,S,d)."""
    if interpret is None:
        interpret = not _on_tpu()
    return _flash_attention(q, k, v, causal=causal, scale=scale,
                            block_q=block_q, block_k=block_k,
                            interpret=interpret)


@functools.partial(jax.jit, static_argnames=("kv_len", "scale", "block_s",
                                             "interpret"))
def decode_attention(q, k, v, mask=None, *, kv_len=None, kv_lens=None,
                     scale=None, block_s=512, interpret=None):
    """Single-pool decode attention (local stage + intra-device reduction).

    q: (B, H, d); k/v: (B, H_kv, S, d); mask: (B, S); kv_lens: optional
    per-sequence (B,) dynamic lengths. Returns (B, H, d).
    """
    if interpret is None:
        interpret = not _on_tpu()
    o, m, l = _flash_decode(q, k, v, mask, kv_len=kv_len, kv_lens=kv_lens,
                            scale=scale, block_s=block_s,
                            interpret=interpret)
    return osm.finalize(osm.AttnPartial(o, m, l), out_dtype=q.dtype)


def decode_attention_partial(q, k, v, mask=None, *, kv_len=None,
                             kv_lens=None, scale=None, block_s=512,
                             interpret=None) -> osm.AttnPartial:
    """Local stage only — returns the merged per-pool partial (for the
    inter-tier / inter-device reduction). Shapes as ``decode_attention``;
    partial fields are (B, H, d) / (B, H), with ``m == -inf`` on a row
    that has no live token (the merge identity)."""
    if interpret is None:
        interpret = not _on_tpu()
    o, m, l = _flash_decode(q, k, v, mask, kv_len=kv_len, kv_lens=kv_lens,
                            scale=scale, block_s=block_s,
                            interpret=interpret)
    return _kernel_partial(o, m, l)


def _kernel_partial(o, m, l) -> osm.AttnPartial:
    """A kernel's (o, m, l) as an ``AttnPartial``: the kernels' finite
    NEG_INF max of an empty row becomes the algebra's -inf identity."""
    return osm.AttnPartial(o=o, m=jnp.where(l > 0, m, -jnp.inf), l=l)


def masked_decode_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                            participate: jax.Array | None,
                            kv_lens: jax.Array, *, scale=None,
                            use_kernel: bool | None = None,
                            block_s: int = 512
                            ) -> tuple[jax.Array, jax.Array]:
    """Repeat-free GQA decode attention + per-token attention mass.

    The single decode-attention entry point for the serving fast path:
    q: (B, H, d); k/v: (B, H_kv, S, d); participate: (B, S) bool or None
    (PAM sparsity/tier union); kv_lens: (B,). Returns (out (B, H, d),
    mass (B, S)) where ``mass`` is the head-mean, count-scaled softmax mass
    feeding the importance EMA (eq. 7).

    On TPU the local stage runs the Pallas ``flash_decode`` kernel (query
    heads grouped per kv head) and the mass is reconstructed from the merged
    (m, l) statistics with one grouped QK^T; elsewhere a single grouped
    einsum computes scores once and reuses them for both the output and the
    mass — no ``jnp.repeat`` KV expansion on either path.
    """
    if use_kernel is None:
        use_kernel = _on_tpu()
    B, H, d = q.shape
    Hkv, S = k.shape[1], k.shape[2]
    live = jnp.arange(S)[None, :] < kv_lens[:, None]
    if participate is not None:
        live = live & participate
    if not use_kernel:
        from repro.models.attention import grouped_decode_attn
        return grouped_decode_attn(q, k, v, live, scale=scale)

    # kernel path: ragged lengths ride the kernel's kv_lens fold so the
    # participation mask alone is the PAM operand
    part = decode_attention_partial(q, k, v, participate, kv_lens=kv_lens,
                                    scale=scale, block_s=min(block_s, S))
    out = osm.finalize(part, out_dtype=q.dtype)
    # Per-token mass from the merged (m, l): one grouped QK^T, no repeat.
    rep = H // Hkv
    sc = scale if scale is not None else 1.0 / (d ** 0.5)
    qg = q.reshape(B, Hkv, rep, d)
    s = jnp.einsum("bgrd,bgsd->bgrs", qg.astype(jnp.float32),
                   k.astype(jnp.float32)) * sc
    s = jnp.where(live[:, None, None, :], s, -jnp.inf)
    m = part.m.reshape(B, Hkv, rep)
    l = part.l.reshape(B, Hkv, rep)
    m_safe = jnp.where(jnp.isfinite(m), m, 0.0)
    p = jnp.exp(s - m_safe[..., None]) / jnp.maximum(l, 1e-30)[..., None]
    p = jnp.where(jnp.isfinite(s), p, 0.0)
    n_live = jnp.sum(live, axis=-1, keepdims=True).astype(jnp.float32)
    mass = jnp.mean(p, axis=(1, 2)) * n_live
    return out, mass


# ------------------------------------------------------------- paged tiers
def _grouped_partial_from_scores(s: jax.Array, v: jax.Array,
                                 live: jax.Array) -> osm.AttnPartial:
    """Partial (o, m, l) from precomputed grouped scores.

    s: (B, Hkv, rep, S) fp32; v: (B, Hkv, S, d); live: (B, S) bool.
    Returns AttnPartial with o (B, H, d), m/l (B, H).
    """
    B, Hkv, rep, S = s.shape
    d = v.shape[-1]
    s = jnp.where(live[:, None, None, :], s, -jnp.inf)
    m = jnp.max(s, axis=-1)
    m_safe = jnp.where(jnp.isfinite(m), m, 0.0)
    p = jnp.exp(s - m_safe[..., None])
    p = jnp.where(jnp.isfinite(s), p, 0.0)
    l = jnp.sum(p, axis=-1)
    o = jnp.einsum("bgrs,bgsd->bgrd", p, v.astype(jnp.float32))
    return osm.AttnPartial(o=o.reshape(B, Hkv * rep, d),
                           m=m.reshape(B, Hkv * rep),
                           l=l.reshape(B, Hkv * rep))


def _grouped_scores(q: jax.Array, k: jax.Array, scale: float) -> jax.Array:
    """One repeat-free grouped QK^T: q (B, H, d), k (B, Hkv, S, d) ->
    (B, Hkv, rep, S) fp32."""
    B, H, d = q.shape
    Hkv = k.shape[1]
    qg = q.reshape(B, Hkv, H // Hkv, d)
    return jnp.einsum("bgrd,bgsd->bgrs", qg.astype(jnp.float32),
                      k.astype(jnp.float32)) * scale


def paged_decode_attention_partial(q: jax.Array, k_pool: jax.Array,
                                   v_pool: jax.Array,
                                   block_table: jax.Array,
                                   token_mask: jax.Array, *,
                                   block_offset=None,
                                   scale=None, use_kernel: bool | None = None,
                                   interpret: bool | None = None
                                   ) -> osm.AttnPartial:
    """Local stage over a paged pool: merged per-pool partial.

    q: (B, H, d); k_pool/v_pool: (NB+1, bs, Hkv, d) single-layer slices
    (sentinel last); block_table: (B, nb) physical ids; token_mask:
    (B, nb*bs) participation at logical positions (length bound folded
    in). On TPU the Pallas ``flash_decode_paged`` kernel walks the table
    in-grid and skips dead pages; elsewhere a jnp gather through the same
    table is the reference path. Partial fields are (B, H, d) / (B, H).

    ``block_offset`` (PR 10) makes the pool slices SHARD-LOCAL while the
    table keeps global ids: entries outside ``[block_offset,
    block_offset + NB_local)`` are masked out of the partial entirely,
    so per-shard partials merge exactly into the global result
    (Alg. 1 across shards — ``distributed.pam_shard``). May be traced.
    """
    if block_offset is not None:
        # Fold non-local tokens out of the mask so BOTH paths agree: a
        # token whose block lives on another shard contributes the
        # merge identity here and its real weight there.
        nb_local, bs = k_pool.shape[0], k_pool.shape[1]
        inside = ((block_table >= block_offset)
                  & (block_table < block_offset + nb_local))
        token_mask = token_mask & jnp.repeat(inside, bs, axis=1)
        block_table = jnp.where(inside, block_table - block_offset, 0)
    if use_kernel is None:
        use_kernel = _on_tpu()
    if use_kernel:
        if interpret is None:
            interpret = not _on_tpu()
        return _kernel_partial(*_flash_decode_paged(
            q, k_pool, v_pool, block_table, token_mask, scale=scale,
            interpret=interpret))
    from repro.core.pam_interface import paged_gather_logical
    d = q.shape[-1]
    sc = scale if scale is not None else 1.0 / (d ** 0.5)
    gk = paged_gather_logical(k_pool, block_table)  # (B, Hkv, nb*bs, d)
    gv = paged_gather_logical(v_pool, block_table)
    s = _grouped_scores(q, gk, sc)
    return _grouped_partial_from_scores(s, gv, token_mask)


def paged_masked_decode_attention(q: jax.Array, k_cache: jax.Array,
                                  v_cache: jax.Array, k_pool: jax.Array,
                                  v_pool: jax.Array, block_table: jax.Array,
                                  hot_mask: jax.Array, paged_mask: jax.Array,
                                  kv_lens: jax.Array, *,
                                  scale=None, use_kernel: bool | None = None
                                  ) -> tuple[jax.Array, jax.Array]:
    """Tiered decode attention: hot-ring partial ⊕ paged warm/cold partial.

    The paged serving fast path's decode-attention entry point. The hot
    tier reads the dense kernel-ready **ring buffer** (``k_cache``/
    ``v_cache``, (B, Hkv, W, dh) — absolute position p at ring slot
    ``p % W``; W == Smax degenerates to the legacy full-window layout):
    the hot participation mask, given in absolute coordinates
    ``(B, Smax)``, is pulled onto ring coordinates through the rotated
    position map (``flash_decode.ring_position_map``). The warm/cold
    tiers read the shared block pool *through the block table* —
    ``paged_mask`` selects their tokens at logical positions, and only
    blocks with a participating token are touched. The two partials are
    merged exactly (Alg. 1 reduction), so the result equals dense masked
    attention over the union mask whenever the pool mirrors the cache.

    Callers must keep ``hot_mask`` inside the ring window (positions
    ``>= kv_lens - W``); out-of-window hot tokens have no ring slot and
    are silently dropped from the hot partial (the serving engine's tier
    clamp guarantees they were re-tagged onto the paged side).

    Returns (out (B, H, d), mass (B, Smax)) where ``mass`` is the
    head-mean count-scaled softmax mass over the union working set in
    absolute coordinates, reconstructed from the merged (m, l)
    statistics: the hot contribution is scattered back through the ring
    index map, the paged contribution comes from the pool's logical
    gather — one grouped QK^T each, the kernel-path idiom of
    ``masked_decode_attention``.
    """
    from repro.core.pam_interface import paged_gather_logical
    from repro.kernels.flash_decode import (ring_gather_mask,
                                            ring_position_map)
    B, H, d = q.shape
    Hkv, W = k_cache.shape[1], k_cache.shape[2]
    Smax = hot_mask.shape[1]
    rep = H // Hkv
    sc = scale if scale is not None else 1.0 / (d ** 0.5)
    live_len = jnp.arange(Smax)[None, :] < kv_lens[:, None]
    hot = hot_mask & live_len
    pgd = paged_mask & live_len

    # Hot partial over the ring: scores on ring coordinates, participation
    # pulled through the rotated position map.
    with jax.named_scope("attn.hot"):
        ring_pos, ring_valid = ring_position_map(kv_lens, W)
        hot_ring = ring_gather_mask(hot, ring_pos, ring_valid)
        s_ring = _grouped_scores(q, k_cache, sc)       # (B, Hkv, rep, W)
        part = _grouped_partial_from_scores(s_ring, v_cache, hot_ring)

    # Paged partial + logical-order pool scores (the latter also feed the
    # union-mass reconstruction — the pool mirrors every token, so its
    # gathered scores are the absolute-coordinate truth).
    # NOTE: the union-mass reconstruction below needs absolute-coordinate
    # scores for the paged side, which this (reference) formulation takes
    # from a full logical pool gather — O(Smax) per step even when few
    # blocks participate. Folding the mass emission into the Pallas
    # kernel's block walk (so only live pages are scored) is the ROADMAP
    # kernel-fusion follow-on; the partial itself already skips dead
    # pages on the kernel path.
    if use_kernel is None:
        use_kernel = _on_tpu()
    with jax.named_scope("pam.mass"):
        gk = paged_gather_logical(k_pool, block_table)  # (B, Hkv, Smax, d)
        s_pool = _grouped_scores(q, gk, sc)            # (B, Hkv, rep, Smax)
    with jax.named_scope("attn.paged"):
        if use_kernel:
            part_paged = paged_decode_attention_partial(
                q, k_pool, v_pool, block_table, pgd, scale=sc,
                use_kernel=True)
        else:
            gv = paged_gather_logical(v_pool, block_table)
            part_paged = _grouped_partial_from_scores(s_pool, gv, pgd)
    with jax.named_scope("attn.merge"):
        merged = osm.merge_partials(part, part_paged)
        out = osm.finalize(merged, out_dtype=q.dtype)

    # Union mass in absolute coordinates from the merged (m, l).
    with jax.named_scope("pam.mass"):
        m = merged.m.reshape(B, Hkv, rep)
        l = merged.l.reshape(B, Hkv, rep)
        m_safe = jnp.where(jnp.isfinite(m), m, 0.0)
        inv_l = 1.0 / jnp.maximum(l, 1e-30)[..., None]

        def probs(s, mask):
            s = jnp.where(mask[:, None, None, :], s, -jnp.inf)
            p = jnp.exp(s - m_safe[..., None]) * inv_l
            return jnp.where(jnp.isfinite(s), p, 0.0)

        ph = jnp.mean(probs(s_ring, hot_ring), axis=(1, 2))      # (B, W)
        pp = jnp.mean(probs(s_pool, pgd), axis=(1, 2))           # (B, Smax)
        bidx = jnp.arange(B)[:, None]
        scatter_idx = jnp.clip(ring_pos, 0, Smax - 1)
        mass = pp.at[bidx, scatter_idx].add(jnp.where(hot_ring, ph, 0.0))
        hot_eff = jnp.zeros((B, Smax), jnp.int32).at[
            bidx, scatter_idx].max(
            hot_ring.astype(jnp.int32)).astype(bool)   # hot ∩ window, abs
        n_live = jnp.sum(hot_eff | pgd, axis=-1,
                         keepdims=True).astype(jnp.float32)
        return out, mass * n_live


def pam_decode_attention(q: jax.Array,
                         tier_kv: Sequence[tuple[jax.Array, jax.Array]],
                         tier_masks: Sequence[jax.Array | None], *,
                         scale=None, block_s=512,
                         interpret=None) -> jax.Array:
    """Full PAMattention decode over heterogeneous tier pools (Alg. 1).

    tier_kv: [(k_t, v_t)] per tier, each (B, H_kv, S_t, d) — S_t may differ
    per tier (HBM hot pool small & dense, SSD pool large). tier_masks:
    per-tier participation (B, S_t) or None. Exact merge across tiers.
    """
    parts = [
        decode_attention_partial(q, k_t, v_t, msk, scale=scale,
                                 block_s=min(block_s, k_t.shape[2]),
                                 interpret=interpret)
        for (k_t, v_t), msk in zip(tier_kv, tier_masks)
    ]
    acc = parts[0]
    for p in parts[1:]:
        acc = osm.merge_partials(acc, p)           # inter-tier reduction
    return osm.finalize(acc, out_dtype=q.dtype)


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def ssd(x, dt, a, b, c, d_skip, *, chunk=128, interpret=None):
    """Mamba-2 SSD chunked scan. See ``ssd_scan`` for shapes."""
    if interpret is None:
        interpret = not _on_tpu()
    return _ssd_scan(x, dt, a, b, c, d_skip, chunk=chunk,
                     interpret=interpret)
