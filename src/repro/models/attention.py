"""GQA attention (train + decode) with optional qk-norm and RoPE.

Train path uses memory-friendly q-chunked attention (peak intermediate
(B, H, chunk, S) instead of (B, H, S, S)); on TPU the Pallas
``fused_attention`` kernel replaces it via the ``use_kernel`` flag.

Decode attention is injectable: the serving/distributed layer passes a
``decode_attn_fn`` (e.g. PAMattention over tier pools or the shard_map
sequence-sharded form); default is dense local attention.
"""

from __future__ import annotations

import math
from typing import Callable, NamedTuple, Optional

import jax
import jax.numpy as jnp

from repro.models.layers import apply_rope, init_linear, rms_norm

DecodeAttnFn = Callable[..., jax.Array]


class AttnParams(NamedTuple):
    wq: jax.Array               # (d, H*dh)
    wk: jax.Array               # (d, Hkv*dh)
    wv: jax.Array               # (d, Hkv*dh)
    wo: jax.Array               # (H*dh, d)
    q_norm: Optional[jax.Array]  # (dh,) or None
    k_norm: Optional[jax.Array]


def init_attn(key, d: int, n_heads: int, n_kv: int, d_head: int,
              qk_norm: bool, dtype) -> AttnParams:
    ks = jax.random.split(key, 4)
    return AttnParams(
        wq=init_linear(ks[0], d, n_heads * d_head, dtype),
        wk=init_linear(ks[1], d, n_kv * d_head, dtype),
        wv=init_linear(ks[2], d, n_kv * d_head, dtype),
        wo=init_linear(ks[3], n_heads * d_head, d, dtype),
        q_norm=jnp.ones((d_head,), dtype) if qk_norm else None,
        k_norm=jnp.ones((d_head,), dtype) if qk_norm else None,
    )


def _project_qkv(p: AttnParams, x: jax.Array, positions: jax.Array,
                 n_heads: int, n_kv: int, d_head: int, rope_theta: float,
                 rms_eps: float):
    B, S, _ = x.shape
    q = jnp.einsum("bsd,de->bse", x, p.wq).reshape(B, S, n_heads, d_head)
    k = jnp.einsum("bsd,de->bse", x, p.wk).reshape(B, S, n_kv, d_head)
    v = jnp.einsum("bsd,de->bse", x, p.wv).reshape(B, S, n_kv, d_head)
    if p.q_norm is not None:
        q = rms_norm(q, p.q_norm, rms_eps)
        k = rms_norm(k, p.k_norm, rms_eps)
    q = apply_rope(q, positions, rope_theta)
    k = apply_rope(k, positions, rope_theta)
    return q, k, v


def chunked_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                      causal: bool, chunk: int = 512,
                      scale: float | None = None) -> jax.Array:
    """q: (B, S, H, dk); k: (B, S, Hkv, dk); v: (B, S, Hkv, dv).
    fp32 softmax, q-chunked; d_v may differ from d_k (MLA)."""
    B, S, H, dh = q.shape
    Hkv, dv = k.shape[2], v.shape[-1]
    rep = H // Hkv
    if scale is None:
        scale = 1.0 / math.sqrt(dh)
    kh = jnp.moveaxis(k, 2, 1)                         # (B, Hkv, S, dh)
    vh = jnp.moveaxis(v, 2, 1)
    qh = jnp.moveaxis(q, 2, 1).reshape(B, Hkv, rep, S, dh)

    chunk = min(chunk, S)
    pad = (chunk - S % chunk) % chunk
    if pad:
        qh = jnp.pad(qh, ((0, 0),) * 3 + ((0, pad), (0, 0)))
    nchunk = (S + pad) // chunk
    qh = qh.reshape(B, Hkv, rep, nchunk, chunk, dh)
    qh = jnp.moveaxis(qh, 3, 0)                        # (nc, B, Hkv, rep, c, dh)

    kpos = jnp.arange(S)

    def one_chunk(ic, qc):
        # qc: (B, Hkv, rep, chunk, dh)
        s = jnp.einsum("bgrcd,bgsd->bgrcs", qc.astype(jnp.float32),
                       kh.astype(jnp.float32)) * scale
        if causal:
            qpos = ic * chunk + jnp.arange(chunk)
            mask = kpos[None, :] <= qpos[:, None]      # (chunk, S)
            s = jnp.where(mask[None, None, None], s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        p = jnp.where(jnp.isnan(p), 0.0, p)
        from repro.models import perf_flags
        if perf_flags.enabled("bf16_probs"):
            # §Perf: fp32 max/sum for stability, bf16 for the PV matmul —
            # halves the dominant score-materialization bytes
            return jnp.einsum("bgrcs,bgsd->bgrcd", p.astype(jnp.bfloat16),
                              vh.astype(jnp.bfloat16)).astype(q.dtype)
        return jnp.einsum("bgrcs,bgsd->bgrcd", p,
                          vh.astype(jnp.float32)).astype(q.dtype)

    out = jax.lax.map(lambda args: one_chunk(*args),
                      (jnp.arange(nchunk), qh))        # (nc, B, Hkv, rep, c, dv)
    out = jnp.moveaxis(out, 0, 3).reshape(B, Hkv, rep, S + pad, dv)
    if pad:
        out = out[..., :S, :]
    out = out.reshape(B, H, S, dv)
    return jnp.moveaxis(out, 1, 2)                     # (B, S, H, dv)


def sp_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                 causal: bool) -> jax.Array:
    """§Perf ``sp_attn``: q-sequence-sharded attention (ring-attention
    layout under GSPMD). Queries stay sharded on the sequence axis over
    "model"; the (small, GQA) K/V are gathered once; scores/softmax/PV are
    fully LOCAL and S-sharded — per layer the only collectives are the K/V
    gather instead of multi-GB score/activation reshards. q: (B,S,H,dk),
    k/v: (B,S,Hkv,d*)."""
    from jax.sharding import PartitionSpec as P
    B, S, H, dh = q.shape
    Hkv, dv = k.shape[2], v.shape[-1]
    rep = H // Hkv
    scale = 1.0 / math.sqrt(dh)
    mesh = jax.sharding.get_abstract_mesh()
    if "model" in mesh.axis_names:
        dp = tuple(a for a in mesh.axis_names
                   if a in ("pod", "data")) or None
        q = jax.lax.with_sharding_constraint(q, P(dp, "model", None, None))
        k = jax.lax.with_sharding_constraint(k, P(dp, None, None, None))
        v = jax.lax.with_sharding_constraint(v, P(dp, None, None, None))
    qg = q.reshape(B, S, Hkv, rep, dh)
    s = jnp.einsum("bqgrd,bkgd->bgrqk", qg, k,
                   preferred_element_type=jnp.float32) * scale
    if causal:
        pos = jnp.arange(S)
        s = jnp.where(pos[None, :] <= pos[:, None], s, -jnp.inf)
    pr = jax.nn.softmax(s, axis=-1)
    pr = jnp.where(jnp.isnan(pr), 0.0, pr)
    from repro.models import perf_flags
    if perf_flags.enabled("bf16_probs"):
        pr = pr.astype(jnp.bfloat16)
    out = jnp.einsum("bgrqk,bkgd->bqgrd", pr, v,
                     preferred_element_type=jnp.float32)
    return out.reshape(B, S, H, dv).astype(q.dtype)


def attention_train(p: AttnParams, x: jax.Array, *, n_heads: int, n_kv: int,
                    d_head: int, causal: bool, rope_theta: float,
                    rms_eps: float, use_kernel: bool = False,
                    q_chunk: int = 512) -> jax.Array:
    """Full-sequence attention for train/prefill. x: (B, S, d)."""
    from repro.models import perf_flags
    B, S, _ = x.shape
    positions = jnp.broadcast_to(jnp.arange(S), (B, S))
    q, k, v = _project_qkv(p, x, positions, n_heads, n_kv, d_head,
                           rope_theta, rms_eps)
    if use_kernel:
        from repro.kernels import ops as kops
        out = kops.fused_attention(jnp.moveaxis(q, 2, 1),
                                   jnp.moveaxis(k, 2, 1),
                                   jnp.moveaxis(v, 2, 1), causal=causal)
        out = jnp.moveaxis(out, 1, 2)
    elif perf_flags.enabled("sp_attn"):
        out = sp_attention(q, k, v, causal=causal)
    else:
        out = chunked_attention(q, k, v, causal=causal, chunk=q_chunk)
    out = out.reshape(B, S, n_heads * d_head)
    return jnp.einsum("bse,ed->bsd", out, p.wo)


def attention_prefill(p: AttnParams, x: jax.Array, *, n_heads: int,
                      n_kv: int, d_head: int, causal: bool,
                      rope_theta: float, rms_eps: float,
                      q_chunk: int = 512):
    """Like ``attention_train`` but also returns the roped K/V in cache
    layout (B, Hkv, S, dh) so serving can seed the decode cache."""
    B, S, _ = x.shape
    positions = jnp.broadcast_to(jnp.arange(S), (B, S))
    q, k, v = _project_qkv(p, x, positions, n_heads, n_kv, d_head,
                           rope_theta, rms_eps)
    out = chunked_attention(q, k, v, causal=causal, chunk=q_chunk)
    out = out.reshape(B, S, n_heads * d_head)
    out = jnp.einsum("bse,ed->bsd", out, p.wo)
    return out, jnp.moveaxis(k, 2, 1), jnp.moveaxis(v, 2, 1)


def attention_prefill_with_prefix(p: AttnParams, x: jax.Array,
                                  prefix_k: jax.Array, prefix_v: jax.Array,
                                  prefix_len: jax.Array, *, n_heads: int,
                                  n_kv: int, d_head: int, rope_theta: float,
                                  rms_eps: float):
    """Suffix prefill for prefix-cache admissions (chunked-prefill core).

    ``x`` holds only the NOVEL tail of a prompt whose first
    ``prefix_len`` tokens already have cache-resident K/V. Queries are
    roped at absolute positions ``prefix_len + i`` and attend over the
    cached prefix (masked to its live length) concatenated with the
    suffix's own causal window — by causality this reproduces exactly
    what a from-scratch prefill would compute for these positions.

    x: (B, S, d) suffix activations; prefix_k/v: (B, Hkv, P, dh)
    logical cache layout (post-RoPE, live below ``prefix_len``);
    prefix_len: (B,). Returns (out (B, S, d), k, v) with k/v the
    suffix's roped K/V in cache layout (B, Hkv, S, dh) — position
    ``prefix_len + i`` at index i, ready for the pool scatter.
    """
    B, S, _ = x.shape
    positions = prefix_len[:, None] + jnp.arange(S)[None, :]
    q, k, v = _project_qkv(p, x, positions, n_heads, n_kv, d_head,
                           rope_theta, rms_eps)
    P = prefix_k.shape[2]
    rep = n_heads // n_kv
    scale = 1.0 / math.sqrt(d_head)
    qg = jnp.moveaxis(q, 2, 1).reshape(B, n_kv, rep, S, d_head)
    kh = jnp.moveaxis(k, 2, 1)                         # (B, Hkv, S, dh)
    vh = jnp.moveaxis(v, 2, 1)
    s_pre = jnp.einsum("bgrsd,bgpd->bgrsp", qg.astype(jnp.float32),
                       prefix_k.astype(jnp.float32)) * scale
    live = jnp.arange(P)[None, :] < prefix_len[:, None]           # (B, P)
    s_pre = jnp.where(live[:, None, None, None, :], s_pre, -jnp.inf)
    s_suf = jnp.einsum("bgrsd,bgtd->bgrst", qg.astype(jnp.float32),
                       kh.astype(jnp.float32)) * scale
    causal = jnp.arange(S)[None, :] <= jnp.arange(S)[:, None]     # (Sq, Sk)
    s_suf = jnp.where(causal[None, None, None], s_suf, -jnp.inf)
    pr = jax.nn.softmax(jnp.concatenate([s_pre, s_suf], axis=-1), axis=-1)
    pr = jnp.where(jnp.isnan(pr), 0.0, pr)
    out = jnp.einsum("bgrsp,bgpd->bgrsd", pr[..., :P],
                     prefix_v.astype(jnp.float32)) + \
        jnp.einsum("bgrst,bgtd->bgrsd", pr[..., P:],
                   vh.astype(jnp.float32))
    out = out.astype(x.dtype).reshape(B, n_heads, S, d_head)
    out = jnp.moveaxis(out, 1, 2).reshape(B, S, n_heads * d_head)
    return jnp.einsum("bse,ed->bsd", out, p.wo), kh, vh


def grouped_decode_attn(q: jax.Array, k_cache: jax.Array,
                        v_cache: jax.Array, live: jax.Array,
                        scale: float | None = None
                        ) -> tuple[jax.Array, jax.Array]:
    """Repeat-free GQA masked decode attention.

    q: (B, H, dh); caches (B, Hkv, Smax, dh); live: (B, Smax) bool — the
    tokens that participate (length mask already folded in). Returns
    (out (B, H, dh), mass (B, Smax)).

    Query heads are grouped (B, Hkv, rep, dh) against their shared kv head,
    so QK^T is computed once per kv head with no ``jnp.repeat``
    materialization of the cache — the same grouping the Pallas
    ``flash_decode`` kernel uses.
    """
    B, H, dh = q.shape
    Hkv, Smax = k_cache.shape[1], k_cache.shape[2]
    rep = H // Hkv
    if scale is None:
        scale = 1.0 / math.sqrt(dh)
    qg = q.reshape(B, Hkv, rep, dh)
    s = jnp.einsum("bgrd,bgsd->bgrs", qg.astype(jnp.float32),
                   k_cache.astype(jnp.float32)) * scale
    s = jnp.where(live[:, None, None, :], s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    p = jnp.where(jnp.isnan(p), 0.0, p)
    out = jnp.einsum("bgrs,bgsd->bgrd", p, v_cache.astype(jnp.float32))
    n_live = jnp.sum(live, axis=-1, keepdims=True).astype(jnp.float32)
    mass = jnp.mean(p, axis=(1, 2)) * n_live
    return out.reshape(B, H, dh).astype(q.dtype), mass


def dense_decode_attn(q: jax.Array, k_cache: jax.Array, v_cache: jax.Array,
                      kv_lens: jax.Array) -> tuple[jax.Array, jax.Array]:
    """Default decode attention. q: (B, H, dh); caches (B, Hkv, Smax, dh);
    kv_lens: (B,). Returns (out (B, H, dh), mass (B, Smax)).

    ``mass`` is the per-token attention probability mass (head-mean, scaled
    by live-token count) — the per-step score S_i(j) that feeds PAM's
    importance EMA (paper eq. 7). It falls out of the softmax for free.
    """
    Smax = k_cache.shape[2]
    live = jnp.arange(Smax)[None, :] < kv_lens[:, None]          # (B, Smax)
    return grouped_decode_attn(q, k_cache, v_cache, live)


def attention_decode(p: AttnParams, x: jax.Array, k_cache: jax.Array,
                     v_cache: jax.Array, kv_lens: jax.Array, *,
                     n_heads: int, n_kv: int, d_head: int, rope_theta: float,
                     rms_eps: float,
                     decode_attn_fn: DecodeAttnFn = dense_decode_attn,
                     paged: Optional[tuple] = None):
    """One decode step. x: (B, d) current-token activations.

    Writes the new token's K/V at position ``kv_lens`` (per-sequence) and
    attends over ``kv_lens + 1`` tokens. Returns (out (B, d),
    mass (B, Smax), k_cache, v_cache) with updated caches.

    ``paged=(pk, pv, dst_block, dst_slot)`` additionally mirrors the
    appended token into this layer's paged KV pool slice ((NB+1, bs,
    Hkv, dh); dst_block/dst_slot (B,) physical coordinates, inactive rows
    routed to the sentinel block) and calls ``decode_attn_fn`` with the
    pool operands ``(q, k_cache, v_cache, pk, pv, kv_lens)``; the return
    grows to (out, mass, k_cache, v_cache, pk, pv). Keys are cached
    post-RoPE, so pool storage order is free — the block table alone
    recovers logical order.
    """
    B, d = x.shape
    with jax.named_scope("model.qkv"):
        q = jnp.einsum("bd,de->be", x, p.wq).reshape(B, n_heads, d_head)
        k = jnp.einsum("bd,de->be", x, p.wk).reshape(B, n_kv, d_head)
        v = jnp.einsum("bd,de->be", x, p.wv).reshape(B, n_kv, d_head)
        if p.q_norm is not None:
            q = rms_norm(q, p.q_norm, rms_eps)
            k = rms_norm(k, p.k_norm, rms_eps)
        pos = kv_lens                                   # (B,)
        q = apply_rope(q[:, None], pos[:, None], rope_theta)[:, 0]
        k = apply_rope(k[:, None], pos[:, None], rope_theta)[:, 0]

    from repro.models import perf_flags
    if perf_flags.enabled("pam_shard_decode"):
        if paged is not None:
            raise ValueError("paged KV pools and the pam_shard_decode "
                             "perf flag are mutually exclusive")
        # §Perf: fused shard_map — masked local cache write + PAMattention
        # psum merge; avoids GSPMD gathering the sequence-sharded cache for
        # the dynamic scatter
        from repro.distributed.pam_shard import fused_update_decode
        out, mass, k_cache, v_cache = fused_update_decode(
            q, k_cache, v_cache, k, v, kv_lens)
    else:
        # scatter new kv at per-sequence position — modulo the buffer's
        # slot count: a hot-window RING cache (slots < Smax) wraps, so
        # this one write is also the ring eviction (the overwritten
        # token's bytes live on in its mapped pool block); a full-window
        # buffer reduces to the absolute position
        with jax.named_scope("kv.append"):
            bidx = jnp.arange(B)
            slot = pos % k_cache.shape[2]
            k_cache = k_cache.at[bidx, :, slot].set(k)
            v_cache = v_cache.at[bidx, :, slot].set(v)
            if paged is not None:
                pk, pv, dst_block, dst_slot = paged
                pk = pk.at[dst_block, dst_slot].set(k)
                pv = pv.at[dst_block, dst_slot].set(v)
        if paged is not None:
            out, mass = decode_attn_fn(q, k_cache, v_cache, pk, pv,
                                       kv_lens + 1)
            with jax.named_scope("model.attn_out"):
                out = out.reshape(B, n_heads * d_head)
                out = jnp.einsum("be,ed->bd", out, p.wo)
            return out, mass, k_cache, v_cache, pk, pv
        out, mass = decode_attn_fn(q, k_cache, v_cache, kv_lens + 1)
    with jax.named_scope("model.attn_out"):
        out = out.reshape(B, n_heads * d_head)
        return (jnp.einsum("be,ed->bd", out, p.wo), mass, k_cache,
                v_cache)
