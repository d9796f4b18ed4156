"""PAM KV-centric management for the serving engine (paper §6 end-to-end).

Holds, per running sequence: per-token importance (eq. 7 EMA), per-token
tier residency (HBM/DDR/SSD), and the retrieval-sparsity participation
mask. Each decode step:

  1. ``participation()``      -> which tokens are loaded (top-S/c + recency)
  2. model decode step        -> attention out + per-token mass S_i(j)
  3. ``observe(scores)``      -> importance EMA update, append new token
     (new tokens enter the hot tier; overflow demotes the least-important
     hot token — capacity cascade), activation-window tracking (§6.1)
  4. every ``schedule_interval`` steps: Algorithm 2 swaps (vmapped over the
     batch) + migration stats for the perf model (§6.2 interface traffic)

The attention itself runs through ``make_masked_decode_attn`` — exact
masked softmax over participating tokens, which the core/kernels property
tests certify equals the per-tier-partition + hierarchical-merge form of
Alg. 1. Tier residency feeds the latency/energy model (per-tier token
counts = per-tier bytes read).
"""

from __future__ import annotations

import dataclasses
from functools import partial
from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.core import importance as imp_mod
from repro.core import scheduling
from repro.core.tiers import COLD, HOT, WARM


@dataclasses.dataclass(frozen=True)
class PAMManagerConfig:
    max_tokens: int
    hot_capacity: int                # tokens per sequence on HBM
    warm_capacity: int               # tokens per sequence on DDR
    compression: int = 8             # retrieval sparsity (paper: 8x)
    recency_window: int = 32
    lam: float = imp_mod.DEFAULT_LAMBDA
    schedule_interval: int = 4       # decode steps between Alg. 2 runs
    schedule: scheduling.ScheduleConfig = scheduling.ScheduleConfig()
    use_sparsity: bool = True
    use_tiering: bool = True


class PAMState(NamedTuple):
    """Per-batch device-side PAM bookkeeping, donated through the fused
    decode dispatch every step.

    ``block_table`` is the paged-KV mapping of the serving fast path:
    physical pool block per (sequence, logical block), written once at
    admission from the host ``BlockAllocator`` and read by the in-kernel
    gather each step. It is size-0 when the engine runs dense-only.
    Since the pool is shared across tiers, Alg. 2 migrations edit only
    ``tier`` — the table itself never changes during decode.
    """
    importance: jax.Array    # (B, Smax) fp32 — eq. 7 EMA
    tier: jax.Array          # (B, Smax) int32 — HOT/WARM/COLD residency
    step: jax.Array          # scalar int32
    moved_tokens: jax.Array  # scalar int32 — cumulative Alg.2 migrations
    last_hot: jax.Array      # (B, Smax) bool — previous participation set
    block_table: jax.Array   # (B, Smax//bs) int32 physical ids, or (0,)


def init_pam_state(batch: int, max_tokens: int, num_blocks: int = 0,
                   sentinel: int = 0) -> PAMState:
    """Zero state. ``num_blocks`` > 0 sizes the per-sequence block table
    (all entries pointing at the pool's ``sentinel`` trash block)."""
    if num_blocks:
        table = jnp.full((batch, num_blocks), sentinel, jnp.int32)
    else:
        table = jnp.zeros((0,), jnp.int32)
    return PAMState(
        importance=jnp.zeros((batch, max_tokens), jnp.float32),
        tier=jnp.full((batch, max_tokens), COLD, jnp.int32),
        step=jnp.zeros((), jnp.int32),
        moved_tokens=jnp.zeros((), jnp.int32),
        last_hot=jnp.zeros((batch, max_tokens), bool),
        block_table=table,
    )


# --------------------------------------------------------------- attention
def make_masked_decode_attn(participate: jax.Array):
    """Decode-attn factory: masks non-participating tokens (sparsity +
    tier-partition union). participate: (B, Smax) traced array.

    Delegates to the repeat-free grouped GQA path (``ops.
    masked_decode_attention``): Pallas ``flash_decode`` + merge on TPU, a
    single grouped einsum elsewhere — no ``jnp.repeat`` of the KV cache."""
    def d_fn(q, k_cache, v_cache, kv_lens):
        from repro.kernels import ops as kops
        return kops.masked_decode_attention(q, k_cache, v_cache,
                                            participate, kv_lens)

    return d_fn


def make_paged_decode_attn(hot_mask: jax.Array, paged_mask: jax.Array,
                           block_table: jax.Array):
    """Paged decode-attn factory for the block-table fast path.

    ``hot_mask``/``paged_mask``: (B, Smax) — the participation set split
    by tier residency (hot reads stay on the dense kernel-ready cache;
    warm/cold reads gather the shared pool through ``block_table``).
    ``block_table``: (B, nb) physical ids with dead logical blocks
    already remapped onto the sentinel. The pages read are the blocks
    holding at least one ``paged_mask`` token.

    The produced function matches the paged ``decode_attn_fn`` contract
    of ``attention_decode``: ``d_fn(q, kc, vc, pk, pv, kv_lens)`` ->
    (out, mass).
    """
    def d_fn(q, k_cache, v_cache, pk, pv, kv_lens):
        from repro.kernels import ops as kops
        return kops.paged_masked_decode_attention(
            q, k_cache, v_cache, pk, pv, block_table, hot_mask,
            paged_mask, kv_lens)

    return d_fn


def paged_participation_split(participate: jax.Array, tier: jax.Array,
                              lengths: jax.Array, block_size: int,
                              hot_window: int = 0
                              ) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Split one step's participation set by storage tier.

    Returns (hot_mask, paged_mask, block_live): hot tokens read the dense
    hot-tier buffer, warm/cold tokens read the paged pool, and
    ``block_live`` ((B, nb) bool) marks the logical blocks the paged
    gather must touch — ``block_live.sum()`` is the step's pages-read,
    the sparse-read win the benchmarks record.

    ``hot_window`` > 0 is the hot ring's slot count: only positions
    inside the ring window (``>= lengths - hot_window``) have hot-tier
    storage, so hot-tagged tokens outside it fall through to the paged
    side — every participating token is read from exactly one storage.
    0 keeps the legacy full-window split (hot tier sized ``Smax``).
    """
    from repro.serving.paged_kv import token_block_mask
    B, Smax = participate.shape
    pos = jnp.arange(Smax)[None, :]
    valid = pos < lengths[:, None]
    live = participate & valid
    if hot_window:
        in_window = pos >= (lengths[:, None] - hot_window)
        hot_mask = live & (tier == HOT) & in_window
        paged_mask = live & ~((tier == HOT) & in_window)
    else:
        hot_mask = live & (tier == HOT)
        paged_mask = live & (tier != HOT)
    return hot_mask, paged_mask, token_block_mask(paged_mask, block_size)


def make_masked_latent_attn(participate: jax.Array):
    """MLA flavor: masks latent tokens. Signature matches
    ``mla_latent_decode_attn``."""
    def l_fn(q_eff, kv_latent, k_rope, kv_lens, *, scale):
        B, Smax = kv_latent.shape[0], kv_latent.shape[1]
        live = (jnp.arange(Smax)[None, :] < kv_lens[:, None]) & participate
        k_eff = jnp.concatenate([kv_latent, k_rope], axis=-1)
        s = jnp.einsum("bhd,bsd->bhs", q_eff.astype(jnp.float32),
                       k_eff.astype(jnp.float32)) * scale
        s = jnp.where(live[:, None, :], s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        p = jnp.where(jnp.isnan(p), 0.0, p)
        out = jnp.einsum("bhs,bsr->bhr", p, kv_latent.astype(jnp.float32))
        n_live = jnp.sum(live, axis=-1, keepdims=True).astype(jnp.float32)
        mass = jnp.mean(p, axis=1) * n_live
        return out.astype(q_eff.dtype), mass

    return l_fn


# ------------------------------------------------------- pure state updates
# Module-level pure functions so the serving engine can inline the whole
# per-step PAM pipeline (participation -> decode -> observe -> stats) into
# ONE fused, donated jit. ``PAMManager`` methods below are thin jit'd
# wrappers around these for standalone use.

def participation_mask(cfg: PAMManagerConfig, importance: jax.Array,
                       lengths: jax.Array) -> jax.Array:
    """(B, Smax) bool. Top-(len/c) by importance + recency pins."""
    B, Smax = importance.shape
    valid = jnp.arange(Smax)[None, :] < lengths[:, None]
    if not cfg.use_sparsity:
        return valid
    budget = jnp.maximum(lengths // cfg.compression, 1)     # (B,)
    pos = jnp.arange(Smax)[None, :]
    recent = (pos >= (lengths - cfg.recency_window)[:, None]) & valid
    score = jnp.where(valid, importance, -jnp.inf)
    score = jnp.where(recent, jnp.inf, score)
    ranks = jnp.argsort(jnp.argsort(-score, axis=-1), axis=-1)
    sel = (ranks < budget[:, None]) & valid
    return sel | recent


def observe_update(cfg: PAMManagerConfig, state: PAMState,
                   scores: jax.Array, lengths: jax.Array,
                   participate: jax.Array) -> PAMState:
    """After a decode step: EMA update + hot append + capacity cascade
    + (every interval) Algorithm 2."""
    with jax.named_scope("pam.observe"):
        B, Smax = state.importance.shape
        valid = jnp.arange(Smax)[None, :] < lengths[:, None]

        imp = imp_mod.update_importance(state.importance,
                                        jnp.where(valid, scores, 0.0),
                                        lam=cfg.lam)
        # new token (at index lengths-1 after the model appended) -> HOT,
        # seeded with the current max importance (recency prior).
        bidx = jnp.arange(B)
        new_pos = jnp.maximum(lengths - 1, 0)
        tier = state.tier.at[bidx, new_pos].set(HOT)
        imp = imp.at[bidx, new_pos].set(
            jnp.maximum(imp[bidx, new_pos], jnp.max(imp, axis=-1)))

        if cfg.use_tiering:
            # capacity cascade: demote least-important over-capacity tokens
            tier = _enforce_capacity(imp, tier, valid, HOT,
                                     cfg.hot_capacity, WARM)
            tier = _enforce_capacity(imp, tier, valid, WARM,
                                     cfg.warm_capacity, COLD)

            def run_sched(im, ti, va):
                new_t, moved, _ = scheduling.schedule_kv(im, ti, va,
                                                         cfg.schedule)
                return new_t, jnp.sum(moved)

            def maybe_schedule(ti):
                new_t, moved = jax.vmap(run_sched)(imp, ti, valid)
                return new_t, jnp.sum(moved)

            do = (state.step + 1) % cfg.schedule_interval == 0
            with jax.named_scope("pam.schedule"):
                tier, moved = jax.lax.cond(
                    do, maybe_schedule,
                    lambda ti: (ti, jnp.zeros((), jnp.int32)), tier)
        else:
            moved = jnp.zeros((), jnp.int32)

        return PAMState(importance=imp, tier=tier, step=state.step + 1,
                        moved_tokens=state.moved_tokens + moved,
                        last_hot=participate,
                        block_table=state.block_table)


def place_prefill_state(cfg: PAMManagerConfig, state: PAMState,
                        slot: jax.Array, length: jax.Array,
                        table_row: jax.Array | None = None) -> PAMState:
    """Initial placement for one admitted sequence (recency fill-down,
    §4.3): tail -> HOT, middle -> DDR, head -> SSD. ``table_row``
    ((nb,) physical block ids from the host allocator, sentinel-padded)
    installs the sequence's paged-KV block table in the same dispatch."""
    with jax.named_scope("pam.place"):
        Smax = state.importance.shape[1]
        idx = jnp.arange(Smax)
        valid = idx < length
        dist = jnp.maximum(length - 1 - idx, 0)
        tier = jnp.where(dist < cfg.hot_capacity, HOT,
                         jnp.where(dist < cfg.hot_capacity
                                   + cfg.warm_capacity, WARM, COLD))
        imp = jnp.where(valid, 1.0 / (1.0 + dist.astype(jnp.float32)),
                        0.0)
        state = state._replace(
            importance=state.importance.at[slot].set(imp),
            tier=state.tier.at[slot].set(tier.astype(jnp.int32)),
            last_hot=state.last_hot.at[slot].set(False),
        )
        if table_row is not None:
            state = state._replace(
                block_table=state.block_table.at[slot].set(table_row))
        return state


def extract_slot_state(state: PAMState, slot) -> tuple[jax.Array, ...]:
    """One sequence's migratable PAM state: (importance, tier, last_hot)
    rows. The block table row is deliberately excluded — physical block
    ids are device-local and rebuilt by the importing engine's own
    allocator (see ``repro.cluster.migration``)."""
    return (state.importance[slot], state.tier[slot], state.last_hot[slot])


def insert_slot_state(state: PAMState, slot, importance: jax.Array,
                      tier: jax.Array, last_hot: jax.Array,
                      table_row: jax.Array | None = None) -> PAMState:
    """Install one migrated sequence's PAM rows at ``slot`` (the inverse
    of ``extract_slot_state``). ``table_row`` — the *importing* engine's
    freshly-allocated physical block ids — is written when the target
    runs the paged KV path."""
    state = state._replace(
        importance=state.importance.at[slot].set(importance),
        tier=state.tier.at[slot].set(tier),
        last_hot=state.last_hot.at[slot].set(last_hot),
    )
    if table_row is not None:
        state = state._replace(
            block_table=state.block_table.at[slot].set(table_row))
    return state


def tier_read_counts_of(tier: jax.Array, participate: jax.Array
                        ) -> jax.Array:
    """(3,) tokens read per tier this step — bytes = counts x token
    bytes; drives the per-tier roofline in the perf model."""
    return jnp.stack([jnp.sum(participate & (tier == t))
                      for t in (HOT, WARM, COLD)])


def hit_rate_of(last_hot: jax.Array, participate: jax.Array) -> jax.Array:
    """Context locality: fraction of this step's working set that was
    also in the previous step's (paper §3.2)."""
    inter = jnp.sum(last_hot & participate, axis=-1)
    denom = jnp.maximum(jnp.sum(participate, axis=-1), 1)
    return jnp.mean(inter / denom)


# ------------------------------------------------------------------ manager
class PAMManager:
    """Stateless-jit wrapper around PAMState transitions."""

    def __init__(self, cfg: PAMManagerConfig):
        self.cfg = cfg

    # -- step 1: which tokens participate this step -----------------------
    @partial(jax.jit, static_argnames=("self",))
    def participation(self, state: PAMState, lengths: jax.Array
                      ) -> jax.Array:
        return participation_mask(self.cfg, state.importance, lengths)

    # -- steps 3+4: importance update, append, schedule --------------------
    @partial(jax.jit, static_argnames=("self",))
    def observe(self, state: PAMState, scores: jax.Array,
                lengths: jax.Array, participate: jax.Array) -> PAMState:
        return observe_update(self.cfg, state, scores, lengths, participate)

    # -- prefill placement --------------------------------------------------
    @partial(jax.jit, static_argnames=("self",))
    def place_prefill(self, state: PAMState, slot: jax.Array,
                      length: jax.Array) -> PAMState:
        return place_prefill_state(self.cfg, state, slot, length)

    # -- stats for the latency/energy model ---------------------------------
    @partial(jax.jit, static_argnames=("self",))
    def tier_read_counts(self, state: PAMState, participate: jax.Array
                         ) -> jax.Array:
        return tier_read_counts_of(state.tier, participate)

    def hit_rate(self, state: PAMState, participate: jax.Array) -> jax.Array:
        return hit_rate_of(state.last_hot, participate)


def _enforce_capacity(imp, tier, valid, t_from: int, cap: int, t_to: int):
    """Demote lowest-importance tokens of tier ``t_from`` past ``cap``."""
    on = (tier == t_from) & valid                       # (B, S)
    count = jnp.sum(on, axis=-1, keepdims=True)
    score = jnp.where(on, imp, jnp.inf)
    ranks = jnp.argsort(jnp.argsort(score, axis=-1), axis=-1)  # asc
    overflow = jnp.maximum(count - cap, 0)
    demote = on & (ranks < overflow)
    return jnp.where(demote, t_to, tier)
