"""Blocked causal flash attention for prefill and training (TPU Pallas).

Serves the NPU-side prefill path of PAM (§4.3: "During prefill, NPUs run all
operators"). FlashAttention-2 on the TPU memory hierarchy: q/k/v blocks
staged HBM->VMEM by BlockSpec, MXU matmuls on the stored operands (bf16 in
a bf16 model) with f32 accumulation, and the online-softmax state
``(m, l, acc)`` in f32 VMEM scratch carried across the sequential kv-block
grid axis — the same algebra as PAMattention's local stage.

Grid: (batch, q head, q block, kv block), kv innermost and sequential.
Heads a whole number of lanes wide are read in the projections' own
layout, (B, S, H * d) with head ``h`` the lane slice ``[h * d, (h + 1) * d)``,
so no transpose is needed on either side; narrower heads are moved
head-major, (B * H, S, d), where a block spans the whole last dimension as
Mosaic requires. A query head reads kv head ``h // rep`` through the index
map (GQA without repeating K/V).

Work that cannot reach a live row is never issued. A per-row live length
``lens[b]`` is scalar-prefetched: keys at or past it are masked, and under
causality so are queries (padded prompt positions are dead; their rows
read zero). A (q block, kv block) pair wholly above the diagonal or wholly
past ``lens[b]`` is skipped twice over: ``pl.when`` skips its compute, and
the kv index map clamps to the last block the q block reads, so the
pipeline issues no DMA for it. Only blocks that cross the diagonal or the
live length build a mask.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


# the fastest blocks tried (256-2048 a side) on a TPU v5e, at 8,192 and at
# 5,900 live tokens with 16 q / 8 kv heads x 128
DEFAULT_BLOCK_Q = 1024
DEFAULT_BLOCK_K = 1024
LANES = 128             # m and l are kept lane-replicated: (block_q, LANES)
NEG_INF = float(-1e30)  # large-negative instead of -inf: keeps exp() exact-0
                        # without NaN from (-inf) - (-inf)


def _lanes(x: jax.Array, n: int) -> jax.Array:
    """A lane-replicated (rows, LANES) statistic widened to (rows, n)."""
    reps = -(-n // LANES)
    if reps > 1:
        x = jnp.tile(x, (1, reps))
    return x if x.shape[1] == n else x[:, :n]


def _last_kv_block(iq, kv_len, *, causal: bool, block_q: int, block_k: int):
    """The last kv block that q block ``iq`` reads (``kv_len >= 1``)."""
    last = (kv_len - 1) // block_k
    if causal:
        last = jnp.minimum(last, ((iq + 1) * block_q - 1) // block_k)
    return last


def _prefill_kernel(lens_ref, q_ref, k_ref, v_ref, o_ref, m_scr, l_scr,
                    acc_scr, *, scale: float, causal: bool, block_q: int,
                    block_k: int):
    b, iq, ik = pl.program_id(0), pl.program_id(2), pl.program_id(3)
    kv_len = lens_ref[b]
    last = _last_kv_block(iq, kv_len, causal=causal, block_q=block_q,
                          block_k=block_k)
    run = ik <= last
    if causal:
        run = run & (iq * block_q < kv_len)
    # a block that crosses the diagonal or the live length needs a mask
    edge = (ik + 1) * block_k > kv_len
    if causal:
        edge = edge | ((ik + 1) * block_k - 1 > iq * block_q)

    @pl.when(ik == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    def attend(masked: bool):
        q = q_ref[0]                                   # (block_q, d)
        k = k_ref[0]                                   # (block_k, d)
        v = v_ref[0]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        if masked:
            shape = (block_q, block_k)
            kpos = ik * block_k + jax.lax.broadcasted_iota(jnp.int32, shape, 1)
            live = kpos < kv_len
            if causal:
                qpos = iq * block_q + jax.lax.broadcasted_iota(
                    jnp.int32, shape, 0)
                live = live & (kpos <= qpos)
            s = jnp.where(live, s, NEG_INF)
        m_prev = m_scr[...]                            # (block_q, LANES)
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        # every row meets a live key in kv block 0, which runs first, so
        # m_new is finite and a masked score's exp is exactly 0
        p = jnp.exp(s - _lanes(m_new, block_k))
        l_scr[...] = alpha * l_scr[...] + jnp.sum(p, axis=1, keepdims=True)
        m_scr[...] = m_new
        d = acc_scr.shape[-1]
        acc_scr[...] = _lanes(alpha, d) * acc_scr[...] + jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(run & edge)
    def _edge():
        attend(masked=True)

    @pl.when(run & jnp.logical_not(edge))
    def _inner():
        attend(masked=False)

    @pl.when(ik == pl.num_programs(3) - 1)
    def _emit():
        # a skipped (dead) q block leaves l == 0 and acc == 0: it reads 0
        l = l_scr[...]
        inv = 1.0 / jnp.where(l > 0, l, 1.0)
        o_ref[0] = (acc_scr[...] * _lanes(inv, acc_scr.shape[-1])
                    ).astype(o_ref.dtype)


def flash_prefill(q: jax.Array, k: jax.Array, v: jax.Array,
                  lens: jax.Array | None = None, *, causal: bool = True,
                  scale: float | None = None,
                  block_q: int = DEFAULT_BLOCK_Q,
                  block_k: int = DEFAULT_BLOCK_K,
                  interpret: bool = False) -> jax.Array:
    """Blocked flash attention. q: (B, S, H, d); k, v: (B, S, H_kv, d),
    GQA when ``H_kv`` divides ``H``. Returns (B, S, H, d) in q.dtype.

    ``lens``: optional (B,) int32 live length of each row (a prompt
    right-padded to a bucket); keys at or past it are masked, and under
    ``causal`` the query rows at or past it are dead and may read zero.
    ``None`` means all ``S`` are live. The sequence is padded internally to
    a block multiple; padded keys are masked and padded rows sliced off.
    """
    B, S, H, d = q.shape
    H_kv = k.shape[2]
    assert k.shape[:2] == (B, S) and H % H_kv == 0, (q.shape, k.shape)
    rep = H // H_kv
    if scale is None:
        scale = 1.0 / math.sqrt(d)
    if lens is None:
        lens = jnp.full((B,), S, jnp.int32)
    # a row keeps at least its first key live: no block index goes negative
    lens = jnp.clip(jnp.asarray(lens, jnp.int32), 1, S)

    # a block spans S rounded up to whole sublanes (8 rows) when S fits one
    # lane tile, else to whole lane tiles: a kv block is the scores' lanes
    tile = 8 if S <= LANES else LANES
    st = -(-S // tile) * tile
    block_q, block_k = min(block_q, st), min(block_k, st)
    big = max(block_q, block_k)
    assert big % min(block_q, block_k) == 0, (block_q, block_k)
    pad = -S % big
    if pad:
        widths = ((0, 0), (0, pad), (0, 0), (0, 0))
        q, k, v = (jnp.pad(x, widths) for x in (q, k, v))
    Sp = S + pad
    nq, nk = Sp // block_q, Sp // block_k

    lane = d % LANES == 0

    def view(x):        # (B, Sp, n, d) -> the kernel's 3-D operand
        n = x.shape[2]
        if lane:        # head h is the lane slice [h*d, (h+1)*d): free
            return x.reshape(B, Sp, n * d)
        return jnp.moveaxis(x, 2, 1).reshape(B * n, Sp, d)

    def at(b, h, n, i):     # block i of head h (of n) in row b's view
        return (b, i, h) if lane else (b * n + h, i, 0)

    def q_map(b, h, iq, ik, lens_ref):
        if causal:   # a dead q block re-reads the last live one: no DMA
            iq = jnp.minimum(iq, (lens_ref[b] - 1) // block_q)
        return at(b, h, H, iq)

    def kv_map(b, h, iq, ik, lens_ref):
        kv_len = lens_ref[b]
        last = _last_kv_block(iq, kv_len, causal=causal, block_q=block_q,
                              block_k=block_k)
        ik = jnp.minimum(ik, last)
        if causal:   # a dead q block stays on the block its live one ended on
            ik = jnp.where(iq * block_q < kv_len, ik, last)
        return at(b, h // rep, H_kv, ik)

    kernel = functools.partial(_prefill_kernel, scale=scale, causal=causal,
                               block_q=block_q, block_k=block_k)
    qv = view(q)
    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(B, H, nq, nk),
            in_specs=[
                pl.BlockSpec((1, block_q, d), q_map),
                pl.BlockSpec((1, block_k, d), kv_map),
                pl.BlockSpec((1, block_k, d), kv_map),
            ],
            out_specs=pl.BlockSpec(
                (1, block_q, d),
                lambda b, h, iq, ik, lens_ref: at(b, h, H, iq)),
            scratch_shapes=[
                pltpu.VMEM((block_q, LANES), jnp.float32),
                pltpu.VMEM((block_q, LANES), jnp.float32),
                pltpu.VMEM((block_q, d), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct(qv.shape, q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        interpret=interpret,
        name="flash_prefill",
    )(lens, qv, view(k), view(v))
    if lane:
        return out[:, :S].reshape(B, S, H, d)
    return jnp.moveaxis(out.reshape(B, H, Sp, d)[:, :, :S], 1, 2)


def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                    causal: bool = True, scale: float | None = None,
                    block_q: int = DEFAULT_BLOCK_Q,
                    block_k: int = DEFAULT_BLOCK_K,
                    interpret: bool = False) -> jax.Array:
    """``flash_prefill`` in head-major layout, every position live.
    q: (B, H, S, d); k, v: (B, H_kv, S, d). Returns (B, H, S, d)."""
    out = flash_prefill(
        jnp.moveaxis(q, 1, 2), jnp.moveaxis(k, 1, 2), jnp.moveaxis(v, 1, 2),
        causal=causal, scale=scale, block_q=block_q, block_k=block_k,
        interpret=interpret)
    return jnp.moveaxis(out, 2, 1)
