"""Split-KV decode attention kernels — PAMattention's Local_Attention stage
(paper Alg. 1 lines 9-13) as TPU Pallas kernels.

``flash_decode`` (dense): the grid walks one (batch, kv-head) pair's KV
in contiguous *splits* (the paper's bank groups) along a sequential grid
axis, carrying the online-softmax state ``(m, l, acc)`` in VMEM scratch
for the ``rep`` grouped query heads that share the kv head — the
intra-device reduction (the paper's per-bank-group RU chain) happens
in-kernel, and the kernel emits one partial triple ``(O, m, l)`` per
query head. The inter-tier / inter-device reduction merges such
partials with the same algebra (``core.online_softmax``).

``flash_decode_paged`` (paged): the warm/cold tiers store KV in a shared
block pool (``serving.paged_kv``), and the grid walks one sequence's
*logical blocks* along its sequential axis, each cell covering every kv
head of one block. The per-request **block table is a kernel operand**
(scalar-prefetched, so it is resident before the grid cell's DMA is
issued) and the index map dereferences it to pick the physical pool
block — the in-kernel analogue of PagedAttention's table walk, in the
spirit of TokenStack's heterogeneous HBM-PIM runtime. The per-token
participation mask rides along as scalar-prefetched bit words (32 tokens
per int32), so a cell whose block has no participating token skips its
compute entirely: sparse tier reads skip untouched pages (dead table
entries are remapped onto the pool's sentinel block, so their DMAs all
alias one trash page that the pipeline fetches once).

A per-token mask carries PAM's tier/sparsity participation on both
kernels: tokens outside the current tier or unselected by retrieval
sparsity contribute exact-zero weight, so the same kernels serve dense
decode, tiered PAMattention, and sparse attention.

Layouts: dense KV is (B, H_kv, S, d) — sequence-major within a head so a
split is a contiguous VMEM block (the bank-aligned mapping of §6.1); the
paged pool is (num_blocks + 1, block_size, H_kv, d) per layer, sentinel
block last, which the paged kernel views as (num_blocks + 1, block_size,
H_kv * d) — a free reshape whose block's trailing dims equal the
array's, as Mosaic requires; head ``h`` is the lane slice
``[h * d, (h + 1) * d)``.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = float(-1e30)
DEFAULT_BLOCK_S = 512
MASK_WORD_BITS = 32
# in a bf16 model q and k hold bf16 values, which QK^T multiplies exactly
# at the MXU's default precision; the fp32 probabilities need full fp32
_PV_PRECISION = jax.lax.Precision.HIGHEST


def ring_position_map(lengths: jax.Array, window: int, *,
                      start: jax.Array | int = 0,
                      size: int | None = None
                      ) -> tuple[jax.Array, jax.Array]:
    """Rotated position map of the hot-window ring buffer (PR 5).

    The hot tier stores only the last ``window`` tokens of each sequence
    in a ring: absolute position ``p`` lives at ring slot ``p % window``,
    so the per-step append (one write at ``lengths % window``) implicitly
    evicts position ``lengths - window``. This map is the address-
    generation step every ring consumer shares — the hot partial's mask
    gather, the admission-commit scatter, and migration export.

    lengths: (B,) int32 current cache lengths. Returns
    ``(ring_pos (B, size) int32, valid (B, size) bool)`` where
    ``ring_pos[b, j]`` is the absolute position resident in slot
    ``start + j`` (some value ``< lengths[b]`` congruent to that slot
    mod ``window``) and ``valid`` marks slots holding a live token.
    When ``window`` covers the whole cache (``window >= lengths``) the
    map degenerates to the identity on ``[0, lengths)`` — the legacy
    dense layout.

    ``start``/``size`` (PR 10) select a contiguous slot range
    ``[start, start + size)`` of the ring instead of the whole window —
    the address map of one ring SHARD. ``start`` may be traced (a
    ``shard_map`` ``axis_index`` expression); ``size`` is static and
    defaults to ``window``.
    """
    lengths = jnp.asarray(lengths, jnp.int32)
    base = (lengths - window)[:, None]                     # (B, 1)
    slots = (jnp.asarray(start, jnp.int32)
             + jnp.arange(size if size is not None else window,
                          dtype=jnp.int32))[None, :]       # (1, W|size)
    ring_pos = base + ((slots - base) % window)            # in [base, base+W)
    valid = ring_pos >= 0                                  # ring_pos < len
    return ring_pos, valid


def ring_gather_mask(mask: jax.Array, ring_pos: jax.Array,
                     valid: jax.Array) -> jax.Array:
    """Pull a (B, Smax) absolute-coordinate boolean mask onto ring
    coordinates: (B, W) with dead slots False. The hot partial's
    participation operand."""
    smax = mask.shape[-1]
    idx = jnp.clip(ring_pos, 0, smax - 1)
    return valid & jnp.take_along_axis(mask, idx, axis=-1)


def _online_softmax_step(s, live, m_scr, l_scr):
    """Fold one block of scores into the running ``(m, l)`` scratch.

    s: (rows, n) fp32 scores; live: broadcastable to s. Returns the
    block's probabilities at the new running max and the rescale factor
    ``alpha`` the caller applies to its output accumulator. A block with
    no live token leaves the state unchanged (p == 0, alpha == 1)."""
    s = jnp.where(live, s, NEG_INF)
    m_prev = m_scr[...]                                    # (rows, 1)
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
    alpha = jnp.exp(m_prev - m_new)
    p = jnp.where(live, jnp.exp(s - m_new), 0.0)
    l_scr[...] = alpha * l_scr[...] + jnp.sum(p, axis=-1, keepdims=True)
    m_scr[...] = m_new
    return p, alpha


def _init_state(m_scr, l_scr, acc_scr):
    m_scr[...] = jnp.full_like(m_scr, NEG_INF)
    l_scr[...] = jnp.zeros_like(l_scr)
    acc_scr[...] = jnp.zeros_like(acc_scr)


def _decode_kernel(q_ref, k_ref, v_ref, mask_ref, o_ref, m_ref, l_ref,
                   m_scr, l_scr, acc_scr, *, scale: float, block_s: int,
                   kv_len: int):
    isplit = pl.program_id(2)

    @pl.when(isplit == 0)
    def _init():
        _init_state(m_scr, l_scr, acc_scr)

    q = q_ref[0, 0].astype(jnp.float32)            # (rep, d)
    k = k_ref[0, 0].astype(jnp.float32)            # (block_s, d)
    v = v_ref[0, 0].astype(jnp.float32)            # (block_s, d)
    msk = mask_ref[0]                              # (1, block_s) int32

    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
    pos = isplit * block_s + jax.lax.broadcasted_iota(
        jnp.int32, msk.shape, 1)
    live = (pos < kv_len) & (msk != 0)
    p, alpha = _online_softmax_step(s, live, m_scr, l_scr)
    acc_scr[...] = alpha * acc_scr[...] + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())), precision=_PV_PRECISION,
        preferred_element_type=jnp.float32)

    @pl.when(isplit == pl.num_programs(2) - 1)
    def _emit():
        # an all-masked row leaves the merge identity (m=NEG_INF, l=o=0)
        o_ref[0, 0] = acc_scr[...]
        m_ref[0, 0] = m_scr[...]
        l_ref[0, 0] = l_scr[...]


def flash_decode(q: jax.Array, k: jax.Array, v: jax.Array,
                 mask: jax.Array | None = None, *,
                 kv_len: int | None = None,
                 kv_lens: jax.Array | None = None,
                 scale: float | None = None,
                 block_s: int = DEFAULT_BLOCK_S,
                 interpret: bool = False
                 ) -> tuple[jax.Array, jax.Array, jax.Array]:
    """PAMattention local stage over a dense cache, merged over splits.

    q: (B, H, d); k, v: (B, H_kv, S, d); mask: (B, S) participation.
    ``kv_len`` is a static whole-batch length bound; ``kv_lens`` an optional
    per-sequence (B,) dynamic length (ragged continuous batching) that is
    folded into the participation mask without re-tracing per length.
    Returns the partial (o, m, l): o (B, H, d) fp32 unnormalized, m/l
    (B, H) fp32 (``m == NEG_INF``, ``l == 0`` for a row with no live
    token). Normalize with ``core.online_softmax.finalize``.
    """
    B, H, d = q.shape
    _, H_kv, S, _ = k.shape
    rep = H // H_kv
    if scale is None:
        scale = 1.0 / math.sqrt(d)
    if kv_len is None:
        kv_len = S
    if mask is None:
        mask = jnp.ones((B, S), jnp.int32)
    else:
        mask = mask.astype(jnp.int32)
    if kv_lens is not None:
        live = jnp.arange(S)[None, :] < kv_lens[:, None]
        mask = mask * live.astype(jnp.int32)

    block_s = min(block_s, max(S, 8))
    pad = (block_s - S % block_s) % block_s
    if pad:
        k = jnp.pad(k, ((0, 0), (0, 0), (0, pad), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, 0), (0, pad), (0, 0)))
        mask = jnp.pad(mask, ((0, 0), (0, pad)))
    nsplit = (S + pad) // block_s

    qg = q.reshape(B, H_kv, rep, d)
    # (B, 1, S): the mask block (1, block_s) keeps its trailing dims
    # either equal to the array's or lane-aligned, as Mosaic requires
    mask = mask[:, None, :]

    kernel = functools.partial(_decode_kernel, scale=scale, block_s=block_s,
                               kv_len=kv_len)

    o, m, l = pl.pallas_call(
        kernel,
        grid=(B, H_kv, nsplit),
        in_specs=[
            pl.BlockSpec((1, 1, rep, d), lambda b, h, s: (b, h, 0, 0)),
            pl.BlockSpec((1, 1, block_s, d), lambda b, h, s: (b, h, s, 0)),
            pl.BlockSpec((1, 1, block_s, d), lambda b, h, s: (b, h, s, 0)),
            pl.BlockSpec((1, 1, block_s), lambda b, h, s: (b, 0, s)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, rep, d), lambda b, h, s: (b, h, 0, 0)),
            pl.BlockSpec((1, 1, rep, 1), lambda b, h, s: (b, h, 0, 0)),
            pl.BlockSpec((1, 1, rep, 1), lambda b, h, s: (b, h, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, H_kv, rep, d), jnp.float32),
            jax.ShapeDtypeStruct((B, H_kv, rep, 1), jnp.float32),
            jax.ShapeDtypeStruct((B, H_kv, rep, 1), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((rep, 1), jnp.float32),
            pltpu.VMEM((rep, 1), jnp.float32),
            pltpu.VMEM((rep, d), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
        name="flash_decode",
    )(qg, k, v, mask)

    return o.reshape(B, H, d), m.reshape(B, H), l.reshape(B, H)


# ------------------------------------------------------------- paged kernel
def _pack_mask_bits(mask: jax.Array, block_size: int) -> jax.Array:
    """(B, nb * block_size) bool -> (B * nb * words,) int32 bit words.

    Token ``j`` of logical block ``i`` of row ``b`` is bit ``j % 32`` of
    word ``(b * nb + i) * words + j // 32`` (``words = ceil(block_size /
    32)``) — the paged kernel's scalar-prefetched participation operand.
    """
    B, S = mask.shape
    nb = S // block_size
    words = -(-block_size // MASK_WORD_BITS)
    m = mask.reshape(B, nb, block_size)
    pad = words * MASK_WORD_BITS - block_size
    if pad:
        m = jnp.pad(m, ((0, 0), (0, 0), (0, pad)))
    m = m.reshape(B, nb, words, MASK_WORD_BITS).astype(jnp.uint32)
    bits = jnp.sum(m << jnp.arange(MASK_WORD_BITS, dtype=jnp.uint32),
                   axis=-1, dtype=jnp.uint32)        # distinct bits: OR
    return jax.lax.bitcast_convert_type(bits, jnp.int32).reshape(-1)


def _paged_decode_kernel(bt_ref, bits_ref, q_ref, k_ref, v_ref,
                         o_ref, m_ref, l_ref, m_scr, l_scr, acc_scr, *,
                         scale: float, n_kv: int, rep: int, head_dim: int,
                         block_size: int, words: int):
    del bt_ref                                     # consumed by index maps
    b, i = pl.program_id(0), pl.program_id(1)
    nb = pl.num_programs(1)
    base = (b * nb + i) * words

    @pl.when(i == 0)
    def _init():
        _init_state(m_scr, l_scr, acc_scr)

    block_words = [bits_ref[base + w] for w in range(words)]
    any_live = block_words[0] != 0
    for w in block_words[1:]:
        any_live = any_live | (w != 0)

    @pl.when(any_live)
    def _attend():
        tok = jax.lax.broadcasted_iota(jnp.int32, (1, block_size), 1)
        word = jnp.full((1, block_size), block_words[0], jnp.int32)
        for w in range(1, words):
            word = jnp.where(tok // MASK_WORD_BITS == w, block_words[w],
                             word)
        live = (jax.lax.shift_right_logical(word, tok % MASK_WORD_BITS)
                & 1) != 0                          # (1, block_size)

        q = q_ref[0].astype(jnp.float32)           # (H, d)
        k = k_ref[0].astype(jnp.float32)           # (block_size, Hkv*d)
        v = v_ref[0].astype(jnp.float32)
        # query row r belongs to kv head r // rep: score every head's
        # keys against all H rows (the MXU pads rows to a tile anyway)
        # and keep each row's own head — no sublane slicing in-kernel
        head_of_row = jax.lax.broadcasted_iota(
            jnp.int32, (n_kv * rep, 1), 0) // rep
        heads = [slice(h * head_dim, (h + 1) * head_dim)
                 for h in range(n_kv)]
        s = jnp.zeros((n_kv * rep, block_size), jnp.float32)
        for h, cols in enumerate(heads):
            s_h = jax.lax.dot_general(
                q, k[:, cols], (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)
            s = jnp.where(head_of_row == h, s_h, s)
        p, alpha = _online_softmax_step(s * scale, live, m_scr, l_scr)
        pv = jnp.zeros(acc_scr.shape, jnp.float32)
        for h, cols in enumerate(heads):
            pv = pv + jax.lax.dot_general(
                jnp.where(head_of_row == h, p, 0.0), v[:, cols],
                (((1,), (0,)), ((), ())), precision=_PV_PRECISION,
                preferred_element_type=jnp.float32)
        acc_scr[...] = alpha * acc_scr[...] + pv

    @pl.when(i == nb - 1)
    def _emit():
        o_ref[0] = acc_scr[...]
        m_ref[0] = m_scr[...]
        l_ref[0] = l_scr[...]


def flash_decode_paged(q: jax.Array, k_pool: jax.Array, v_pool: jax.Array,
                       block_table: jax.Array, mask: jax.Array, *,
                       scale: float | None = None,
                       interpret: bool = False
                       ) -> tuple[jax.Array, jax.Array, jax.Array]:
    """PAMattention local stage over a paged KV pool (block-table operand).

    q: (B, H, d); k_pool/v_pool: (NB+1, block_size, H_kv, d) single-layer
    pool slices, sentinel block last; block_table: (B, nb) int32 physical
    block per logical block (sentinel for unmapped); mask: (B, nb*bs)
    participation at *logical* positions with any per-sequence length
    bound already folded in.

    ``block_table`` and the bit-packed mask ride the grid as scalar-
    prefetch operands: the k/v index maps dereference the table so each
    grid cell DMAs exactly its physical block, and cells whose block has
    no participating token skip their compute — untouched pages are
    skipped. Their table entries are remapped onto the sentinel so their
    prefetches alias one block.

    Returns the partial merged over the sequence's blocks: o (B, H, d)
    fp32 unnormalized, m/l (B, H) (``m == NEG_INF``, ``l == 0`` for a row
    with no participating token).
    """
    B, H, d = q.shape
    NBp, bs, H_kv, _ = k_pool.shape
    nb = block_table.shape[1]
    rep = H // H_kv
    words = -(-bs // MASK_WORD_BITS)
    if scale is None:
        scale = 1.0 / math.sqrt(d)
    mask = mask.astype(bool)
    live = mask.reshape(B, nb, bs).any(axis=-1)
    # Route dead logical blocks onto the sentinel: their (skipped) cells
    # all alias one physical page instead of touching live data.
    table = jnp.where(live, block_table, NBp - 1).astype(jnp.int32)

    kernel = functools.partial(_paged_decode_kernel, scale=scale, n_kv=H_kv,
                               rep=rep, head_dim=d, block_size=bs,
                               words=words)
    kv_map = lambda b, i, bt, bits: (bt[b * nb + i], 0, 0)
    row_map = lambda b, i, bt, bits: (b, 0, 0)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,                 # block table + mask bits
        grid=(B, nb),
        in_specs=[
            pl.BlockSpec((1, H, d), row_map),
            pl.BlockSpec((1, bs, H_kv * d), kv_map),
            pl.BlockSpec((1, bs, H_kv * d), kv_map),
        ],
        out_specs=[
            pl.BlockSpec((1, H, d), row_map),
            pl.BlockSpec((1, H, 1), row_map),
            pl.BlockSpec((1, H, 1), row_map),
        ],
        scratch_shapes=[
            pltpu.VMEM((H, 1), jnp.float32),
            pltpu.VMEM((H, 1), jnp.float32),
            pltpu.VMEM((H, d), jnp.float32),
        ],
    )
    table_flat, bits = table.reshape(-1), _pack_mask_bits(mask, bs)
    with jax.named_scope("kv.relayout"):
        # the kernel reads each block as one (bs, H_kv * d) tile
        k_flat = k_pool.reshape(NBp, bs, H_kv * d)
        v_flat = v_pool.reshape(NBp, bs, H_kv * d)
    o, m, l = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((B, H, d), jnp.float32),
            jax.ShapeDtypeStruct((B, H, 1), jnp.float32),
            jax.ShapeDtypeStruct((B, H, 1), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
        name="flash_decode_paged",
    )(table_flat, bits, q, k_flat, v_flat)

    return o, m[..., 0], l[..., 0]
