"""Fused prefill/train attention kernel (FlashAttention-2 style, TPU Pallas).

Serves the NPU-side prefill path of PAM (§4.3: "During prefill, NPUs run all
operators"). Tiled for the TPU memory hierarchy: q/k/v blocks staged
HBM->VMEM via BlockSpec, MXU-shaped (multiples of 128) matmuls, fp32
accumulation in VMEM scratch carried across the sequential kv-block grid
axis — the same online-softmax algebra as PAMattention's local stage.

Grid: (batch*heads, q_blocks, kv_blocks) with kv innermost & sequential
("arbitrary"), so the (m, l, acc) scratch implements the running rescale.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


DEFAULT_BLOCK_Q = 128
DEFAULT_BLOCK_K = 128
NEG_INF = float(-1e30)  # large-negative instead of -inf: keeps exp() exact-0
                        # without NaN from (-inf) - (-inf)


def _attn_kernel(q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr, *,
                 scale: float, causal: bool, block_q: int, block_k: int,
                 kv_len: int):
    iq = pl.program_id(1)
    ik = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(ik == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q = q_ref[0, 0].astype(jnp.float32)        # (block_q, d)
    k = k_ref[0, 0].astype(jnp.float32)        # (block_k, d)
    v = v_ref[0, 0].astype(jnp.float32)        # (block_k, d)

    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale

    # mask: causal + kv-length padding
    kpos = ik * block_k + jax.lax.broadcasted_iota(jnp.int32,
                                                   (block_q, block_k), 1)
    mask = kpos < kv_len
    if causal:
        qpos = iq * block_q + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 0)
        mask = mask & (kpos <= qpos)
    s = jnp.where(mask, s, NEG_INF)

    m_prev = m_scr[...]
    m_cur = jnp.max(s, axis=-1)
    m_new = jnp.maximum(m_prev, m_cur)
    alpha = jnp.exp(m_prev - m_new)
    p = jnp.exp(s - m_new[:, None])
    p = jnp.where(mask, p, 0.0)

    l_scr[...] = l_scr[...] * alpha + jnp.sum(p, axis=-1)
    acc_scr[...] = acc_scr[...] * alpha[:, None] + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
    m_scr[...] = m_new

    @pl.when(ik == nk - 1)
    def _finalize():
        l = l_scr[...]
        l_safe = jnp.where(l > 0, l, 1.0)
        o_ref[0, 0] = (acc_scr[...] / l_safe[:, None]).astype(o_ref.dtype)


def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                    causal: bool = True, scale: float | None = None,
                    block_q: int = DEFAULT_BLOCK_Q,
                    block_k: int = DEFAULT_BLOCK_K,
                    interpret: bool = False) -> jax.Array:
    """Fused attention. q: (B, H, S, d); k, v: (B, H_kv, S, d) (GQA ok).

    Returns (B, H, S, d) in q.dtype. Sequence is padded internally to block
    multiples; padding keys are masked, padding queries produce zeros that
    are sliced off.
    """
    B, H, Sq, d = q.shape
    _, H_kv, Sk, _ = k.shape
    assert H % H_kv == 0, (H, H_kv)
    rep = H // H_kv
    if scale is None:
        scale = 1.0 / math.sqrt(d)

    block_q = min(block_q, max(Sq, 8))
    block_k = min(block_k, max(Sk, 8))
    sq_pad = (block_q - Sq % block_q) % block_q
    sk_pad = (block_k - Sk % block_k) % block_k
    if sq_pad:
        q = jnp.pad(q, ((0, 0), (0, 0), (0, sq_pad), (0, 0)))
    if sk_pad:
        k = jnp.pad(k, ((0, 0), (0, 0), (0, sk_pad), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, 0), (0, sk_pad), (0, 0)))
    Sq_p, Sk_p = Sq + sq_pad, Sk + sk_pad
    nq, nk = Sq_p // block_q, Sk_p // block_k

    q4 = q.reshape(B * H, 1, Sq_p, d)
    k4 = k.reshape(B * H_kv, 1, Sk_p, d)
    v4 = v.reshape(B * H_kv, 1, Sk_p, d)

    kernel = functools.partial(
        _attn_kernel, scale=scale, causal=causal, block_q=block_q,
        block_k=block_k, kv_len=Sk)

    def _kv_row(bh, iq, ik):
        # bh = b*H + h  ->  kv row = b*H_kv + h//rep
        return ((bh // H) * H_kv + (bh % H) // rep, 0, ik, 0)

    out = pl.pallas_call(
        kernel,
        grid=(B * H, nq, nk),
        in_specs=[
            pl.BlockSpec((1, 1, block_q, d),
                         lambda bh, iq, ik: (bh, 0, iq, 0)),
            pl.BlockSpec((1, 1, block_k, d), _kv_row),
            pl.BlockSpec((1, 1, block_k, d), _kv_row),
        ],
        out_specs=pl.BlockSpec((1, 1, block_q, d),
                               lambda bh, iq, ik: (bh, 0, iq, 0)),
        out_shape=jax.ShapeDtypeStruct((B * H, 1, Sq_p, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q,), jnp.float32),
            pltpu.VMEM((block_q,), jnp.float32),
            pltpu.VMEM((block_q, d), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(q4, k4, v4)

    out = out.reshape(B, H, Sq_p, d)
    if sq_pad:
        out = out[:, :, :Sq, :]
    return out
