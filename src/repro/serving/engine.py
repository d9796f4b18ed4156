"""The PAM serving engine (paper §4): request pool, continuous batching
with prefill priority, PAM-managed decode loop, SLO accounting.

Control flow is real (host Python over jit'd device steps, like vLLM's
scheduler over CUDA graphs); *hardware timing* is injectable — pass a
``latency_model`` (see ``repro.perfmodel``) to account each step at the
modeled speed of a PAM / L-PIM / vLLM-offloading system, which is exactly
the paper's simulator methodology. Without one, wall-clock is used.

Decode fast path
----------------
The whole per-step PAM pipeline — participation mask, masked decode step,
step-score -> importance EMA, tier-read/hit-rate counters, Alg. 2 (under a
``schedule_interval`` cond) and greedy sampling — is ONE ``jax.jit`` with
``donate_argnums`` for the KV cache, the PAM state and the token vector:
a decode step is a single device dispatch with zero cache copies, and the
host only reads back a small ``StepBufs`` stats/tokens struct. Tokens stay
on device between steps (the sampled token feeds the next dispatch without
a host round-trip), ``run()`` consumes step *t-1*'s buffers while step *t*
runs (async dispatch), and ``micro_steps > 1`` wraps a ``lax.fori_loop``
micro-loop around the fused body so the host is visited only once every k
steps. Sampling is on-device too: ``temperature``/``top_k`` with
PER-REQUEST keys derived in-dispatch as ``fold_in(fold_in(seed, rid),
position)`` (0 = exact greedy argmax) — a request's sampled stream is a
pure function of (seed, rid, positions, logits), independent of batch
composition, slot or step phase, which is what makes migration and
failure replay bit-exact even at temperature > 0 — and ``eos_token >=
0`` folds EOS detection into the dispatch — a slot that samples EOS drops
out of the ``active`` carry, so the micro-loop serves EOS traffic as well.
Prefill lengths are bucketed to powers of two (capping jit-cache blowup)
and admissions sharing a bucket commit as a GROUP: one batched prefill +
one donated multi-slot dispatch for cache scatter + PAM placement + token
seeds.

Cluster hooks
-------------
``export_request``/``import_request`` detach and re-admit a RUNNING
request mid-decode (inter-device KV migration, paper §4.3/§6.2): export
gathers the request's KV into the portable logical layout — hot tokens
from the dense cache, warm/cold THROUGH the block table — and frees the
slot and pool blocks without finishing; import is one donated
admission-style dispatch on the target. ``load_signal``/``can_accept``/
``slot_importance_mass`` feed the router and balancer cost signals
(``repro.cluster``).

Paged warm/cold tiers
---------------------
With ``ServingConfig.block_size > 0`` the warm/cold tiers additionally
live on a shared ``PagedKVPool`` (paper §4.2.2): a host ``BlockAllocator``
maps each request to physical pool blocks at admission (one table write
per request — never per step), the table rides ``PAMState.block_table``
through the donated dispatch, and the fused step splits the participation
set by tier: hot tokens read the dense kernel-ready cache, warm/cold
tokens are gathered from the pool *through the block table* (a kernel
operand — ``flash_decode_paged`` on TPU, a jnp table gather elsewhere)
so pages with no participating token are never touched. Both partials
merge exactly (Alg. 1), the single-dispatch/donation invariants are
unchanged, and ``StepBufs`` additionally reports pages touched vs. the
dense window for the sparse-read accounting. Pool capacity is admission
backpressure: requests wait (instead of erroring) until finished
sequences free their blocks, so a pool smaller than ``max_batch``'s
worst case overcommits gracefully.

Prefix sharing (PR 7)
---------------------
With ``ServingConfig.prefix_cache`` the pool becomes REFCOUNTED and a
``PrefixTrie`` keyed on token ids indexes every committed prompt's
blocks. Admission looks up the prompt's longest cached prefix, ADOPTS
those physical blocks into the new table (refcount +1 — zero prefill
compute for the shared part), prefills only the novel suffix
(``prefill_suffix`` attends over the pool-gathered prefix; exact by
causality), and commits in one donated dispatch. A partially-filled
shared tail block is always duplicated into a fresh block BEFORE the
suffix scatter (copy-on-write); fully-shared interior blocks are never
copied and never written — appends land strictly above the shared
prefix by construction. ``free`` is a decref everywhere (finish,
export, preemption), so shared blocks outlive any individual owner; the
trie holds its own reference per block, which is what keeps prefixes
cached after their publisher finishes, and LRU-evicts trie-only blocks
under pool pressure. Tier-tag migration (Alg. 2) is per-request
metadata, so sharers can tag the same physical block differently —
shared bytes are never touched. Token streams are twin-exact with
from-scratch admission (greedy and sampled — the per-request sampling
keys don't see any of this).

Hot-window ring (PR 5)
----------------------
With ``ServingConfig.hot_window > 0`` the dense hot-tier buffer shrinks
from ``(L, B, Hkv, max_len, dh)`` to a RING ``(L, B, Hkv, W, dh)``:
absolute position ``p`` lives at ring slot ``p % W``, so per-slot
hot-tier bytes are independent of ``max_len`` — the paper's §4.1-4.2
capacity argument (only the hot window needs dense high-bandwidth
storage; warm/cold tokens live ONLY in pool blocks). The per-step
append is one ring write whose overwrite IS the eviction (the evicted
token was mirrored into its mapped pool block when it was appended, in
the same donated dispatch), demotion completes as a tier-tag clamp, and
promotion of an in-window token needs no copy at all — the ring already
holds every in-window position, so Alg. 2 promotions just flip which
storage the split reads. The hot partial reads the ring through the
rotated position map (``kernels.flash_decode.ring_position_map``) and
merges with the paged partial exactly (Alg. 1), so token streams are
bit-for-bit those of the full-window engine; admission commit,
migration export/import and the micro-loop are all rebased onto ring
coordinates while participation, importance and block tables stay
absolute.
"""

from __future__ import annotations

import collections
import dataclasses
import functools
import time
import warnings
from typing import Any, Iterable, Iterator, NamedTuple, Optional, Callable

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import pam_interface as pam_if
from repro.core import tiers as tiers_mod
from repro.frontend.chunking import ChunkPlan, validate_budget
from repro.core.tiers import HOT
from repro.kernels.flash_decode import ring_position_map
from repro.models import transformer as tf
from repro.models.config import ModelConfig
from repro.obs import metrics as obs_metrics
from repro.obs import trace as obs_trace
from repro.serving import pam_manager as pm
from repro.serving import paged_kv as pkv
from repro.serving.paged_kv import (BlockAllocator, OutOfBlocks,
                                    PrefixTrie)
from repro.serving.pam_manager import (PAMManager, PAMManagerConfig,
                                       init_pam_state,
                                       make_masked_decode_attn,
                                       make_masked_latent_attn)

WAITING, PREFILLING, RUNNING, DONE = (
    "waiting", "prefilling", "running", "done")


@dataclasses.dataclass
class Request:
    id: int
    prompt: np.ndarray                 # (S,) int32
    max_new_tokens: int
    arrival: float = 0.0


@dataclasses.dataclass
class RequestState:
    request: Request
    status: str = WAITING
    slot: int = -1
    outputs: list[int] = dataclasses.field(default_factory=list)
    planned: int = 0                   # tokens dispatched (>= len(outputs))
    first_token_time: Optional[float] = None
    finish_time: Optional[float] = None
    token_times: list[float] = dataclasses.field(default_factory=list)
    submit_clock: float = 0.0          # engine clock at submit


@dataclasses.dataclass(frozen=True)
class ServingConfig:
    """Engine configuration.

    ``block_size > 0`` turns on the paged warm/cold KV path: the pool
    holds ``pool_blocks`` physical blocks of ``block_size`` tokens
    (default: enough for every slot's full window, i.e. no overcommit;
    set it lower to exercise capacity backpressure). Requires a PAM
    config (tier residency decides dense-vs-paged reads) and a GQA-cache
    model family, and ``max_len`` must be a block multiple.

    ``hot_window > 0`` (paged mode only) shrinks the dense hot-tier
    buffer to a RING of that many slots — absolute position ``p`` lives
    at ring slot ``p % hot_window`` — so per-slot hot-tier bytes are
    ``O(hot_window)`` instead of ``O(max_len)``. Every appended token is
    mirrored into its mapped pool block in the same donated dispatch, so
    the append's ring overwrite IS the eviction (the evicted token's
    only live copy becomes its pool block, where warm/cold reads already
    go); token streams are exactly those of the full-window engine.
    0 keeps the legacy full-window buffer (a ring with ``max_len``
    slots, i.e. the identity rotation).
    """
    max_batch: int = 4
    max_len: int = 256
    eos_token: int = -1                # -1: run to max_new_tokens
    pam: Optional[PAMManagerConfig] = None   # None -> dense baseline
    micro_steps: int = 1               # decode steps fused per dispatch
    bucket_prefill: bool = True        # pow-2 prompt-length buckets
    block_size: int = 0                # paged-KV block tokens (0 = dense)
    pool_blocks: Optional[int] = None  # physical blocks (None = full)
    hot_window: int = 0                # hot ring slots (0 = max_len)
    temperature: float = 0.0           # 0 = greedy argmax (exact tests)
    top_k: int = 0                     # 0 = full softmax when sampling
    sample_seed: int = 0               # per-request sampling key seed:
    # token at position p of request rid draws from
    # fold_in(fold_in(PRNGKey(sample_seed), rid), p)
    prefix_cache: bool = False         # trie-indexed prompt-prefix
    # sharing over the paged pool (PR 7): admission maps a prompt's
    # longest cached prefix onto existing physical blocks (refcounted,
    # zero prefill compute for the shared part), prefills only the novel
    # suffix, and copy-on-writes a partially-filled shared tail block
    # before its first divergent write. Requires block_size > 0 and a
    # token-only GQA family. Off by default: the engine is then
    # bit-identical to PR 6.
    prefill_chunk: int = 0             # chunked prefill budget (PR 8):
    # a prompt whose novel part exceeds this many tokens admits in
    # bounded power-of-two slices interleaved with decode steps — each
    # slice is ONE fused dispatch appending its KV through the pool
    # commit path, the final slice rides the suffix-commit path (hot-row
    # rebuild + first-token sample), and no engine step ever prefills
    # more than `prefill_chunk` tokens per in-flight admission. Token
    # streams are bit-identical to single-shot admission. Requires the
    # paged pool (block_size > 0) and a token-only GQA family; must be a
    # power of two. 0 = off (single-shot prefill, PR 7 behavior).


class StepBufs(NamedTuple):
    """Per-dispatch device->host readback: k fused decode steps' tokens and
    stats. Small — the only thing the host ever copies back per step."""
    tokens: jax.Array       # (k, B) int32 greedy samples per fused step
    tier_reads: jax.Array   # (k, 3) int32 participating tokens per tier
    hit_rate: jax.Array     # (k,)   f32 context-locality hit rate
    moved: jax.Array        # (k,)   int32 Alg. 2 migrations this step
    lengths: jax.Array      # (k, B) int32 post-step cache lengths
    blocks: jax.Array       # (k, 2) int32 (paged pages touched, dense
                            #               window pages) — paged mode


# ---------------------------------------------------- shared jit builders
# Compiled executables are keyed by (model config, PAM config, shapes) at
# module level, NOT per engine instance: constructing a second engine with
# the same configuration reuses the compiled fused step instead of paying
# compile again (configs are frozen dataclasses, hence hashable).

def _sample_tokens(logits, seed: int, rids, positions,
                   temperature: float, top_k: int):
    """On-device sampling: greedy argmax when ``temperature == 0``
    (static — compiles to the exact PR-1 fast path), else temperature
    softmax with optional top-k filtering. Each row draws from its own
    PER-REQUEST key ``fold_in(fold_in(PRNGKey(seed), rid), position)``
    — the sampled token at absolute position ``p`` of request ``rid``
    depends only on (seed, rid, p) and the logits, never on batch
    composition, slot index or the engine's global step history. That
    replay-stability is what makes sampled streams bit-identical across
    migration AND failure recovery (a replayed request regenerates the
    exact tokens it already emitted — ``repro.cluster.recovery``)."""
    with jax.named_scope("model.sample"):
        if temperature == 0.0:
            return jnp.argmax(logits, axis=-1).astype(jnp.int32)
        lg = logits.astype(jnp.float32) / temperature
        if 0 < top_k < lg.shape[-1]:
            kth = jax.lax.top_k(lg, top_k)[0][..., -1:]
            lg = jnp.where(lg < kth, -jnp.inf, lg)
        base = jax.random.PRNGKey(seed)

        def draw(rid, pos, row):
            key = jax.random.fold_in(jax.random.fold_in(base, rid), pos)
            return jax.random.categorical(key, row, axis=-1)

        return jax.vmap(draw)(rids.astype(jnp.uint32),
                              positions.astype(jnp.uint32),
                              lg).astype(jnp.int32)


def _fused_decode_body(cfg: ModelConfig, pcfg: Optional[PAMManagerConfig],
                       smax: int, bs: int, sentinel: int,
                       temperature: float, top_k: int, eos: int,
                       hot_window: int, seed: int, mesh,
                       params, tokens, cache, pam_state, active, rids):
    """ONE decode step of the full PAM pipeline, pure & traceable:
    participation -> masked decode -> stats -> observe -> sample.

    ``bs`` > 0 selects the paged warm/cold path: the participation set is
    split by tier, warm/cold reads gather the pool through
    ``pam_state.block_table`` (dead pages remapped to ``sentinel``), and
    the appended token is mirrored into its mapped block.

    ``hot_window`` > 0 is the hot ring's slot count: hot-tier tags are
    first clamped to the ring window (a token the append evicted cannot
    stay hot — demotion is the ring overwrite plus this tag edit), the
    participation split confines hot reads to in-window tokens, and the
    dense append in ``attention_decode`` wraps modulo the window. All
    other coordinates (participation, importance EMA, block tables) stay
    absolute.

    ``eos >= 0`` folds EOS detection into the dispatch: a slot that
    samples EOS is deactivated *on device* (returned ``active`` drops
    it), so the multi-step micro-loop can serve eos traffic without a
    host check between fused steps — finished slots freeze their cache
    lengths and token for the remaining micro-steps.
    """
    B = active.shape[0]
    with jax.named_scope("pam.participation"):
        lengths = cache.lengths + active.astype(jnp.int32)
        if pcfg is not None:
            participate = pm.participation_mask(
                pcfg, pam_state.importance, lengths)
        else:
            participate = jnp.arange(smax)[None, :] < lengths[:, None]
    l_fn = make_masked_latent_attn(participate)
    paged_append = None
    blocks = jnp.zeros((2,), jnp.int32)
    if bs:
        nb = smax // bs
        with jax.named_scope("pam.participation"):
            if hot_window:
                # ring demotion, part 2: the append overwrote the evicted
                # slot; re-tag tokens that slid out of the window so the
                # split (and the tier accounting) reads them from the pool
                pam_state = pam_state._replace(
                    tier=tiers_mod.clamp_hot_to_window(
                        pam_state.tier, lengths, hot_window))
            hot_m, pgd_m, block_live = pm.paged_participation_split(
                participate, pam_state.tier, lengths, bs, hot_window)
            bt_eff = jnp.where(block_live, pam_state.block_table,
                               sentinel)
        if mesh is not None:
            # PR 10: hot ring + pool reads fan out over the mesh's
            # "model" axis under shard_map; partials re-merge with the
            # exact online-softmax (pmax/psum of (O, m, l)) so the
            # sharded step is bit-identical to the unsharded one
            from repro.distributed import pam_shard as psh
            d_fn = psh.make_sharded_paged_decode_attn(
                mesh, hot_m, pgd_m, bt_eff)
        else:
            d_fn = pm.make_paged_decode_attn(hot_m, pgd_m, bt_eff)
        # append coordinates for the new token (same for every layer);
        # inactive rows write the sentinel trash page
        with jax.named_scope("kv.append"):
            pos = cache.lengths
            lb = jnp.clip(pos // bs, 0, nb - 1)
            dst_block = jnp.where(
                active, pam_state.block_table[jnp.arange(B), lb], sentinel)
            paged_append = (dst_block.astype(jnp.int32),
                            (pos % bs).astype(jnp.int32))
        with jax.named_scope("pam.stats"):
            valid = jnp.arange(smax)[None, :] < lengths[:, None]
            window = pkv.token_block_mask(valid, bs)
            act = active[:, None]
            blocks = jnp.stack([jnp.sum(block_live & act),
                                jnp.sum(window & act)]).astype(jnp.int32)
    else:
        d_fn = make_masked_decode_attn(participate)
    old_lens = cache.lengths
    logits, cache, scores = tf.decode_step(
        cfg, params, tokens, cache, decode_attn_fn=d_fn,
        latent_attn_fn=l_fn, paged_append=paged_append)
    with jax.named_scope("kv.append"):
        # inactive slots: freeze their lengths
        cache = cache._replace(
            lengths=jnp.where(active, cache.lengths, old_lens))

    if pcfg is not None:
        with jax.named_scope("pam.stats"):
            read_mask = participate & active[:, None]
            tier_reads = pm.tier_read_counts_of(pam_state.tier, read_mask)
            hit = pm.hit_rate_of(pam_state.last_hot, participate)
        if scores is None:     # attention-free: recency-only scores
            scores = (jnp.arange(smax)[None, :]
                      == (cache.lengths - 1)[:, None]).astype(jnp.float32)
        before = pam_state.moved_tokens
        pam_state = pm.observe_update(pcfg, pam_state, scores,
                                      cache.lengths, participate)
        with jax.named_scope("pam.stats"):
            moved = pam_state.moved_tokens - before
    else:
        tier_reads = jnp.zeros((3,), jnp.int32)
        hit = jnp.zeros((), jnp.float32)
        moved = jnp.zeros((), jnp.int32)

    with jax.named_scope("model.head"):
        # the sampled token's absolute position is the post-append cache
        # length — the (rid, position) pair keys the per-request PRNG
        nxt = _sample_tokens(logits, seed, rids, cache.lengths,
                             temperature, top_k)
        tokens = jnp.where(active, nxt, tokens)
        if eos >= 0:
            active = active & (tokens != eos)   # EOS emitted -> freeze
    return tokens, cache, pam_state, active, (tier_reads, hit, moved,
                                              cache.lengths, blocks)


@functools.lru_cache(maxsize=None)
def _fused_decode_fn(cfg: ModelConfig, pcfg: Optional[PAMManagerConfig],
                     smax: int, batch: int, k: int, bs: int = 0,
                     sentinel: int = 0, temperature: float = 0.0,
                     top_k: int = 0, eos: int = -1, hot_window: int = 0,
                     seed: int = 0, mesh=None, cache_shardings=None):
    """Fused decode dispatch running ``k`` steps on device. Cache (dense
    buffers AND paged pools), PAM state (including the block table) and
    the token vector are DONATED — zero per-step copies. ``rids`` is the
    per-slot request-id vector: sampling keys derive on device as
    ``fold_in(fold_in(seed, rid), position)``, so no PRNG state is
    threaded between dispatches at all (the key is a pure function of
    what the request is and where it is in its stream — replayable).
    The active mask rides the micro-loop carry so on-device EOS
    detection (``eos >= 0``) freezes finished slots mid-dispatch."""
    def run_k(params, tokens, cache, pam_state, active, rids):
        bufs = StepBufs(
            tokens=jnp.zeros((k, batch), jnp.int32),
            tier_reads=jnp.zeros((k, 3), jnp.int32),
            hit_rate=jnp.zeros((k,), jnp.float32),
            moved=jnp.zeros((k,), jnp.int32),
            lengths=jnp.zeros((k, batch), jnp.int32),
            blocks=jnp.zeros((k, 2), jnp.int32))

        def step_i(i, carry):
            tokens, cache, pam_state, active, bufs = carry
            tokens, cache, pam_state, active, \
                (reads, hit, moved, lens, blk) = _fused_decode_body(
                    cfg, pcfg, smax, bs, sentinel, temperature, top_k,
                    eos, hot_window, seed, mesh, params, tokens, cache,
                    pam_state, active, rids)
            with jax.named_scope("pam.stats"):
                bufs = StepBufs(
                    tokens=bufs.tokens.at[i].set(tokens),
                    tier_reads=bufs.tier_reads.at[i].set(reads),
                    hit_rate=bufs.hit_rate.at[i].set(hit),
                    moved=bufs.moved.at[i].set(moved),
                    lengths=bufs.lengths.at[i].set(lens),
                    blocks=bufs.blocks.at[i].set(blk))
            return tokens, cache, pam_state, active, bufs

        carry = (tokens, cache, pam_state, active, bufs)
        if k == 1:
            carry = step_i(0, carry)
        else:
            carry = jax.lax.fori_loop(0, k, step_i, carry)
        tokens, cache, pam_state, active, bufs = carry
        return tokens, cache, pam_state, bufs

    if cache_shardings is not None:
        # pin outputs so donation stays shape-AND-layout compatible
        # across steps: the cache keeps its shard layout, everything
        # else stays replicated (``lengths`` is always replicated, so
        # its sharding doubles as the replicated spec)
        rep = cache_shardings.lengths
        return jax.jit(run_k, donate_argnums=(1, 2, 3),
                       out_shardings=(rep, cache_shardings, rep, rep))
    return jax.jit(run_k, donate_argnums=(1, 2, 3))


@functools.lru_cache(maxsize=None)
def _prefill_fn(cfg: ModelConfig, smax: int, rep=None):
    # one jit per (cfg, smax); jax retraces per prompt-bucket shape
    # SSM/hybrid prompts are never padded (bucket == exact length),
    # so the dynamic-length machinery is skipped entirely.
    # Returns LOGITS (not a token): the admission commit samples the
    # first token under the same temperature/top-k/PRNG policy as the
    # fused decode dispatch.
    exact = cfg.family in ("ssm", "hybrid")

    def pre(params, tokens, true_len):
        logits, cache = tf.prefill(cfg, params, tokens, smax,
                                   true_len=None if exact else true_len)
        return logits, cache

    if rep is not None:
        # sharded engines: the prefill SUB-cache feeds the (replicated-
        # operand) admission commit — pin it replicated so GSPMD never
        # invents a layout the commit has to rematerialize away from
        return jax.jit(pre, out_shardings=(rep, rep))
    return jax.jit(pre)


@functools.lru_cache(maxsize=None)
def _admit_commit_fn(pcfg: Optional[PAMManagerConfig], block_size: int,
                     n: int, temperature: float = 0.0, top_k: int = 0,
                     hot_window: int = 0, seed: int = 0,
                     cache_shardings=None):
    """One donated dispatch per admission GROUP: scatter ``n`` prefilled
    sequences (one batched prefill's sub-cache) into their slots, SAMPLE
    each first token from the prefill logits (same temperature/top-k/
    per-request-key policy as the decode dispatch), seed the device
    token vector and place each sequence's initial tier layout. In paged mode
    (``block_size`` > 0) the same dispatch also scatters each prompt's
    KV into its allocated pool blocks and installs its block-table row.
    With a hot ring (``hot_window`` > 0) the dense scatter is rebased
    onto ring coordinates: only each prompt's last ``hot_window`` tokens
    land in the ring (through the rotated position map), while the pool
    write above keeps every token — older prompt positions exist ONLY in
    their pool blocks from the moment of admission.
    ``n == 1`` is the single-admission case; same-bucket admission
    bursts ride one dispatch."""
    def admit_commit(cache, pam_state, tokens_dev, sub, logits, slots,
                     lengths, rids, table_rows=None):
        # first token = absolute position `prompt_len` of request `rid`
        firsts = _sample_tokens(logits, seed, rids, lengths,
                                temperature, top_k)
        def put(full, batch_rows):
            if full.ndim == 0 or full.size == 0:
                return full
            if full.ndim == 1:                      # lengths (B,) <- (n,)
                return full.at[slots].set(batch_rows)
            return full.at[:, slots].set(batch_rows)    # (L, B, ...)
        if block_size:
            # pool fields have no batch axis — peel them off the generic
            # per-slot scatter and fill them through the block tables
            # (full logical rows, BEFORE any ring re-layout of sub)
            pk, pv = cache.pk, cache.pv
            for i in range(n):
                pk = pkv.write_prefill(pk, sub.k[:, i], table_rows[i],
                                       block_size)
                pv = pkv.write_prefill(pv, sub.v[:, i], table_rows[i],
                                       block_size)
            if hot_window:
                with jax.named_scope("kv.ring_relayout"):
                    ring_pos, valid = ring_position_map(lengths,
                                                        hot_window)
                    ring_of = jax.vmap(pam_if.logical_to_ring,
                                       in_axes=(1, 0, 0), out_axes=1)
                    sub = sub._replace(k=ring_of(sub.k, ring_pos, valid),
                                       v=ring_of(sub.v, ring_pos, valid))
            with jax.named_scope("kv.slot_install"):
                cache = cache._replace(pk=sub.pk, pv=sub.pv)
                cache = jax.tree.map(put, cache, sub)
                cache = cache._replace(pk=pk, pv=pv)
        else:
            with jax.named_scope("kv.slot_install"):
                cache = jax.tree.map(put, cache, sub)
        with jax.named_scope("model.sample"):
            tokens_dev = tokens_dev.at[slots].set(firsts)
        if pcfg is not None:
            for i in range(n):
                pam_state = pm.place_prefill_state(
                    pcfg, pam_state, slots[i], lengths[i],
                    table_rows[i] if block_size else None)
        return cache, pam_state, tokens_dev, firsts

    if cache_shardings is not None:
        rep = cache_shardings.lengths
        return jax.jit(admit_commit, donate_argnums=(0, 1, 2),
                       out_shardings=(cache_shardings, rep, rep, rep))
    return jax.jit(admit_commit, donate_argnums=(0, 1, 2))


@functools.lru_cache(maxsize=None)
def _suffix_prefill_fn(cfg: ModelConfig, smax: int, rep=None):
    """Batched suffix-only prefill dispatch (PR 7 path, batched in
    PR 8): gather each row's cached prefix from the pool THROUGH its
    block table (the §6.2 sharer-side re-layout — a pure read of the
    shared blocks), then run ``tf.prefill_suffix`` over just the novel
    tokens of every row at once. Rows with ``prefix_len == 0`` are
    plain admissions riding the same dispatch — the gathered prefix is
    all zeros and masked inside attention, so their result is exactly
    the from-scratch prefill. One dispatch; retraces per (group size,
    suffix bucket) like ``_prefill_fn``. Returns (last-token logits
    (n, V), suffix K/V (L, n, Hkv, S, dh))."""
    def suffix_pre(params, tokens, pk, pv, read_rows, prefix_lens,
                   true_lens):
        with jax.named_scope("kv.prefix_gather"):
            gather = jax.vmap(pam_if.gather_prefix_logical,
                              in_axes=(None, 0, 0), out_axes=1)
            gk = gather(pk, read_rows, prefix_lens)  # (L, n, Hkv, P, dh)
            gv = gather(pv, read_rows, prefix_lens)
        return tf.prefill_suffix(cfg, params, tokens, gk, gv,
                                 prefix_lens, true_len=true_lens)

    if rep is not None:
        return jax.jit(suffix_pre, out_shardings=(rep, rep, rep))
    return jax.jit(suffix_pre)


@functools.lru_cache(maxsize=None)
def _suffix_commit_fn(pcfg: Optional[PAMManagerConfig], block_size: int,
                      n: int, temperature: float = 0.0, top_k: int = 0,
                      hot_window: int = 0, seed: int = 0,
                      cache_shardings=None):
    """ONE donated dispatch committing a suffix-prefill admission GROUP
    (prefix-cache hits, the plain same-bucket admissions batched with
    them, and final chunked-prefill slices):

    1. Copy-on-write each row's shared, partially-filled tail block
       (``cow_srcs[i]``, still owned by its publisher/trie) into that
       row's fresh ``cow_dsts[i]`` BEFORE any write. Rows with nothing
       to copy pass the sentinel for both — a self-copy of the trash
       block, i.e. a no-op. Fully-shared interior blocks are never
       copied: the table maps them directly.
    2. Scatter each row's novel-suffix K/V token-by-token into its
       fresh blocks (pad positions routed to the sentinel trash block).
    3. Rebuild each slot's dense hot row by gathering the FULL logical
       sequence back through its table (shared prefix + fresh suffix),
       re-based onto ring coordinates when ``hot_window`` is set.
    4. Sample each first token at absolute position ``lengths[i]``
       under the same per-request-key policy as every other dispatch,
       and place the PAM rows + block tables.

    The donation/one-dispatch invariants match ``_admit_commit_fn``: a
    burst of n same-bucket admissions costs 2 dispatches whether or not
    any of them hit the prefix cache."""
    def suffix_commit(cache, pam_state, tokens_dev, suf_k, suf_v, logits,
                      slots, lengths, rids, table_rows, bids, sids,
                      cow_srcs, cow_dsts):
        pk, pv = cache.pk, cache.pv
        for i in range(n):
            pk = pkv.copy_block(pk, cow_srcs[i], cow_dsts[i])
            pv = pkv.copy_block(pv, cow_srcs[i], cow_dsts[i])
        with jax.named_scope("kv.prefill_write"):
            sk = jnp.moveaxis(suf_k, 2, 3)         # (L, n, S, Hkv, dh)
            sv = jnp.moveaxis(suf_v, 2, 3)
            pk = pk.at[:, bids, sids].set(sk)      # bids/sids: (n, S)
            pv = pv.at[:, bids, sids].set(sv)
        with jax.named_scope("kv.ring_relayout"):
            gat = jax.vmap(pkv.gather_sequence, in_axes=(None, 0),
                           out_axes=1)
            gk = gat(pk, table_rows)               # (L, n, Hkv, smax, dh)
            gv = gat(pv, table_rows)
            live = (jnp.arange(gk.shape[3])[None, None, None, :, None]
                    < lengths[None, :, None, None, None])
            gk = jnp.where(live, gk, jnp.zeros((), gk.dtype))
            gv = jnp.where(live, gv, jnp.zeros((), gv.dtype))
            if hot_window:
                ring_pos, valid = ring_position_map(lengths, hot_window)
                ring_of = jax.vmap(pam_if.logical_to_ring,
                                   in_axes=(1, 0, 0), out_axes=1)
                dk = ring_of(gk, ring_pos, valid)
                dv = ring_of(gv, ring_pos, valid)
            else:
                dk, dv = gk, gv
        with jax.named_scope("kv.slot_install"):
            cache = cache._replace(
                k=cache.k.at[:, slots].set(dk),
                v=cache.v.at[:, slots].set(dv),
                lengths=cache.lengths.at[slots].set(lengths),
                pk=pk, pv=pv)
        firsts = _sample_tokens(logits, seed, rids, lengths,
                                temperature, top_k)
        with jax.named_scope("model.sample"):
            tokens_dev = tokens_dev.at[slots].set(firsts)
        if pcfg is not None:
            for i in range(n):
                pam_state = pm.place_prefill_state(
                    pcfg, pam_state, slots[i], lengths[i],
                    table_rows[i])
        return cache, pam_state, tokens_dev, firsts

    if cache_shardings is not None:
        rep = cache_shardings.lengths
        return jax.jit(suffix_commit, donate_argnums=(0, 1, 2),
                       out_shardings=(cache_shardings, rep, rep, rep))
    return jax.jit(suffix_commit, donate_argnums=(0, 1, 2))


@functools.lru_cache(maxsize=None)
def _chunk_fill_fn(cfg: ModelConfig, smax: int, cow: bool = False,
                   cache_shardings=None):
    """ONE donated dispatch advancing a chunked-prefill admission by an
    INTERMEDIATE slice (PR 8): optionally copy-on-write the shared tail
    block (first slice of a prefix-cache hit), gather the already-
    filled prefix ``[0, begin)`` from the pool through the request's
    own table, run the suffix prefill over just this slice's tokens,
    and scatter the slice's K/V into the mapped pool blocks. No dense
    hot row, no sampling, no PAM placement — those happen once, in the
    FINAL slice's suffix commit, after which the request is
    indistinguishable from a single-shot admission. The slice logits
    are discarded (only the final slice's feed sampling)."""
    def chunk_fill(params, cache, tokens, table_row, begin, true_len,
                   bids, sids, cow_src, cow_dst):
        pk, pv = cache.pk, cache.pv
        if cow:
            # after the copy the request's own table maps cow_dst, which
            # now holds the shared tail bytes — the gather below reads
            # the prefix entirely through the request's own row
            pk = pkv.copy_block(pk, cow_src, cow_dst)
            pv = pkv.copy_block(pv, cow_src, cow_dst)
        with jax.named_scope("kv.prefix_gather"):
            gk = pam_if.gather_prefix_logical(pk, table_row, begin)
            gv = pam_if.gather_prefix_logical(pv, table_row, begin)
        _, suf_k, suf_v = tf.prefill_suffix(
            cfg, params, tokens, gk[:, None], gv[:, None], begin[None],
            true_len=true_len)
        with jax.named_scope("kv.prefill_write"):
            sk = jnp.moveaxis(suf_k[:, 0], 1, 2)   # (L, S, Hkv, dh)
            sv = jnp.moveaxis(suf_v[:, 0], 1, 2)
            pk = pk.at[:, bids, sids].set(sk)
            pv = pv.at[:, bids, sids].set(sv)
        return cache._replace(pk=pk, pv=pv)

    if cache_shardings is not None:
        return jax.jit(chunk_fill, donate_argnums=(1,),
                       out_shardings=cache_shardings)
    return jax.jit(chunk_fill, donate_argnums=(1,))


@functools.lru_cache(maxsize=None)
def _import_commit_fn(has_pam: bool, block_size: int,
                      hot_window: int = 0, cache_shardings=None):
    """One donated dispatch per migrated-request import: install the
    snapshot's logical-layout KV into the dense cache slot (and, in
    paged mode, scatter it through the target's freshly-allocated block
    table — the §6.2 address-generation/receiver step), insert the PAM
    rows and seed the device token vector. With a hot ring the dense
    install is re-based onto ring coordinates (last ``hot_window``
    tokens through the rotated position map; the pool scatter below
    keeps the full context). The admission twin of ``export``: a
    migrated request resumes with zero host state left on the source."""
    def import_commit(cache, pam_state, tokens_dev, k_row, v_row, imp_row,
                      tier_row, lh_row, slot, length, token,
                      table_row=None):
        with jax.named_scope("kv.ring_relayout"):
            if hot_window:
                ring_pos, valid = ring_position_map(length[None],
                                                    hot_window)
                dk = pam_if.logical_to_ring(k_row, ring_pos[0], valid[0])
                dv = pam_if.logical_to_ring(v_row, ring_pos[0], valid[0])
            else:
                dk, dv = k_row, v_row
        with jax.named_scope("kv.slot_install"):
            cache = cache._replace(
                k=cache.k.at[:, slot].set(dk),
                v=cache.v.at[:, slot].set(dv),
                lengths=cache.lengths.at[slot].set(length))
        if block_size:
            cache = cache._replace(
                pk=pkv.write_prefill(cache.pk, k_row, table_row,
                                     block_size),
                pv=pkv.write_prefill(cache.pv, v_row, table_row,
                                     block_size))
        tokens_dev = tokens_dev.at[slot].set(token)
        if has_pam:
            with jax.named_scope("pam.place"):
                pam_state = pm.insert_slot_state(
                    pam_state, slot, imp_row, tier_row, lh_row,
                    table_row if block_size else None)
        return cache, pam_state, tokens_dev

    if cache_shardings is not None:
        rep = cache_shardings.lengths
        return jax.jit(import_commit, donate_argnums=(0, 1, 2),
                       out_shardings=(cache_shardings, rep, rep))
    return jax.jit(import_commit, donate_argnums=(0, 1, 2))


@functools.lru_cache(maxsize=None)
def _export_gather_fn(block_size: int, hot_window: int = 0):
    """Snapshot gather for inter-device migration (§6.2 sender side):
    hot tokens read the dense cache row, warm/cold tokens are gathered
    from the pool THROUGH the block table (``paged_kv.gather_sequence``)
    — one fused gather producing the portable logical (L, Hkv, Smax, dh)
    layout. With a hot ring, the hot rows stream through the rotated
    ring index map (``ring_to_logical``) on top of the pool gather — the
    snapshot layout is unchanged, so engines with different (or no) hot
    windows interoperate. Dense-only engines just slice the cache."""
    @jax.jit
    @jax.named_scope("kv.export_gather")
    def export_gather(k, v, pk, pv, table_row, tier_row, slot, length):
        kc, vc = k[:, slot], v[:, slot]       # (L, Hkv, Smax|W, dh)
        if not block_size:
            return kc, vc
        gk = pkv.gather_sequence(pk, table_row)
        gv = pkv.gather_sequence(pv, table_row)
        if hot_window:
            ring_pos, valid = ring_position_map(length[None], hot_window)
            ring_pos, valid = ring_pos[0], valid[0]
            smax = gk.shape[2]
            hot_at = jnp.take(tier_row, jnp.clip(ring_pos, 0, smax - 1))
            sel = valid & (hot_at == HOT)
            return (pam_if.ring_to_logical(kc, ring_pos, sel, gk),
                    pam_if.ring_to_logical(vc, ring_pos, sel, gv))
        hot = (tier_row == HOT)[None, None, :, None]
        return jnp.where(hot, kc, gk), jnp.where(hot, vc, gv)

    return export_gather


class ServingEngine:
    """The PAM serving engine (alias ``PAMEngine``).

    Construct with a model config + params and a ``ServingConfig``;
    ``submit`` requests, then drive with ``step()`` (synchronous, one
    fused dispatch per call) or ``run()`` (to completion; pipelined
    multi-step micro-loop when ``micro_steps > 1``). See the module
    docstring for the fused-dispatch, donation, and paged-tier
    invariants, and ``summary()`` for the metrics contract.
    """

    def __init__(self, spec, params=None, scfg: Optional[ServingConfig]
                 = None,
                 latency_model: Optional[Callable[[dict], float]] = None,
                 name: Optional[str] = None,
                 devices: Optional[tuple] = None):
        # canonical construction is EngineSpec.build(params) — the spec
        # carries model + serving config + shard + name declaratively.
        # The legacy (cfg, params, scfg, ...) positional signature still
        # works through this shim, with a DeprecationWarning.
        from repro.serving.spec import EngineSpec
        if isinstance(spec, EngineSpec):
            if scfg is not None or name is not None:
                raise TypeError(
                    "ServingEngine(EngineSpec, params, ...): serving "
                    "config and name live on the spec; pass only "
                    "latency_model as a keyword")
        else:
            warnings.warn(
                "ServingEngine(cfg, params, scfg, ...) is deprecated; "
                "use EngineSpec(model=cfg, serving=scfg, name=...)"
                ".build(params, latency_model=...)",
                DeprecationWarning, stacklevel=2)
            if scfg is None:
                raise TypeError("legacy ServingEngine(cfg, params, scfg)"
                                " signature requires a ServingConfig")
            spec = EngineSpec(model=spec, serving=scfg,
                              name=name if name is not None else "dev0")
        cfg, scfg = spec.model, spec.serving
        assert cfg.has_decode, f"{cfg.name} is encoder-only"
        self.spec = spec
        self.cfg = cfg
        self.params = params
        self.scfg = scfg
        self.latency_model = latency_model
        self.name = spec.name                  # cluster device handle
        self.shard = spec.shard
        if devices is not None and len(devices) != spec.shard:
            raise ValueError(f"shard={spec.shard} engine given "
                             f"{len(devices)} devices")
        # the devices this engine's params, cache and state live on
        # (None: JAX's default device, or the first `shard` devices)
        self.devices = None if devices is None else tuple(devices)
        self.mesh = None                       # set when spec.shard > 1
        self.cache_shardings = None
        self.clock = 0.0                       # simulated seconds
        self.busy_time = 0.0                   # sim seconds with active>0
        self.last_step_time = 0.0              # modeled latency, last step
        self.last_step_stats = None            # stats of that decode step

        B, Smax = scfg.max_batch, scfg.max_len
        self.pam_cfg = scfg.pam
        self.mgr = PAMManager(scfg.pam) if scfg.pam else None
        self.block_size = scfg.block_size
        self.hot_window = scfg.hot_window
        self.allocator: Optional[BlockAllocator] = None
        self.sentinel = 0
        if self.hot_window and not self.block_size:
            raise ValueError("hot_window (ring hot tier) requires the "
                             "paged pool (block_size > 0): evicted "
                             "tokens live only in their mapped blocks")
        if self.hot_window and not 0 < self.hot_window <= Smax:
            raise ValueError(f"hot_window {self.hot_window} must be in "
                             f"(0, max_len={Smax}]")
        if self.block_size:
            if scfg.pam is None:
                raise ValueError("paged KV (block_size > 0) requires a "
                                 "PAM config: tier residency decides "
                                 "dense-vs-paged reads")
            if Smax % self.block_size:
                raise ValueError(f"max_len {Smax} not a multiple of "
                                 f"block_size {self.block_size}")
            nb_seq = Smax // self.block_size
            if scfg.pool_blocks is not None and scfg.pool_blocks <= 0:
                raise ValueError(f"pool_blocks must be positive, got "
                                 f"{scfg.pool_blocks}")
            pool_blocks = (scfg.pool_blocks if scfg.pool_blocks is not None
                           else B * nb_seq)
            self.allocator = BlockAllocator(pool_blocks, self.block_size)
            self.sentinel = pool_blocks
            self.cache = tf.init_decode_cache(
                cfg, B, Smax, paged_blocks=pool_blocks,
                block_size=self.block_size, hot_window=self.hot_window)
            self.pam_state = init_pam_state(B, Smax, num_blocks=nb_seq,
                                            sentinel=pool_blocks)
            self.peak_occupancy = 0.0
            self.blocks_touched_total = 0
            self.blocks_window_total = 0
        else:
            self.cache = tf.init_decode_cache(cfg, B, Smax)
            self.pam_state = init_pam_state(B, Smax)

        if spec.shard > 1:
            # PR 10: tensor-shard params and sequence-shard KV over one
            # shared device group. Params are GSPMD-sharded (a replica
            # GROUP holds ONE copy, ~1/shard bytes per device); the hot
            # ring splits on its slot axis and the pool on its physical-
            # block axis (``serving_cache_shardings``). The fused step
            # pins its out_shardings so donation keeps the layout.
            spec.validate()
            from repro.distributed import pam_shard as psh
            from repro.distributed import sharding as shd
            if self.devices is None:
                local = jax.devices()
                if len(local) < spec.shard:
                    raise ValueError(
                        f"shard={spec.shard} needs {spec.shard} local "
                        f"XLA devices but only {len(local)} present; on "
                        f"CPU relaunch under XLA_FLAGS=--xla_force_host_"
                        f"platform_device_count={max(spec.shard, 8)} "
                        f"(must be set before jax is imported)")
                self.devices = tuple(local[:spec.shard])
            self.mesh = psh.decode_mesh(self.devices)
            self.params = jax.device_put(
                params, shd.param_shardings(cfg, self.mesh))
            self.cache_shardings = shd.serving_cache_shardings(
                self.mesh, self.cache)
            rep = self.cache_shardings.lengths
            self.cache = jax.device_put(self.cache, self.cache_shardings)
            self.pam_state = jax.device_put(
                self.pam_state, jax.tree.map(lambda _: rep,
                                             self.pam_state))
        elif self.devices is not None:
            # a one-device replica commits its state to its own device;
            # every dispatch then runs where its operands live
            dev = self.devices[0]
            self.params = jax.device_put(params, dev)
            self.cache = jax.device_put(self.cache, dev)
            self.pam_state = jax.device_put(self.pam_state, dev)

        self.trie: Optional[PrefixTrie] = None
        if scfg.prefix_cache:
            if not self.block_size:
                raise ValueError("prefix_cache requires the paged pool "
                                 "(block_size > 0): shared prefixes live "
                                 "in refcounted blocks")
            if cfg.family == "vlm":
                raise ValueError("prefix_cache keys on token ids; the "
                                 "vlm patch prefix has none")
            self.trie = PrefixTrie(self.block_size, self.allocator)
            self.prefix_hits = 0            # admissions with matched > 0
            self.cached_prefix_tokens = 0   # prefill compute skipped
            self.novel_prefill_tokens = 0   # prefill compute performed
            self.cow_copies = 0             # tail blocks duplicated

        self.chunk = scfg.prefill_chunk
        self._chunking: dict[int, ChunkPlan] = {}  # rid -> in-flight plan
        if self.chunk:
            validate_budget(self.chunk)
            if not self.block_size:
                raise ValueError("prefill_chunk (chunked prefill) "
                                 "requires the paged pool (block_size > "
                                 "0): slices append KV through the pool "
                                 "commit path")
            # chunk_slices counts slice dispatches; max_chunk_slice is
            # the largest slice actually prefilled (tests pin <= budget)
            self.chunked_admissions = 0
            self.chunk_slices = 0
            self.max_chunk_slice = 0

        self.requests: dict[int, RequestState] = {}
        self.waiting: collections.deque[int] = collections.deque()
        self.slots: list[Optional[int]] = [None] * B
        self.tokens_dev = jnp.zeros((B,), jnp.int32)  # lives on device
        if self.mesh is not None:
            self.tokens_dev = jax.device_put(
                self.tokens_dev, self.cache_shardings.lengths)
        elif self.devices is not None:
            self.tokens_dev = jax.device_put(self.tokens_dev,
                                             self.devices[0])
        # per-slot request ids: the sampling-key operand of the fused
        # dispatch (keys derive as fold_in(fold_in(seed, rid), position),
        # so no PRNG state survives between dispatches)
        self.rids_host = np.zeros((B,), np.uint32)
        self.steps = 0
        # fast-path observability: one fused dispatch should serve one (or
        # k) decode steps — asserted by tests and reported by benchmarks
        self.decode_dispatches = 0
        self.decode_device_steps = 0
        self.prefill_dispatches = 0
        self.admit_dispatches = 0
        self.migrations_in = 0
        self.migrations_out = 0

        self._micro_jits: dict[int, Any] = {}    # keyed by fused step count
        self._prefill_jit: dict[int, Any] = {}   # keyed by prompt bucket
        self._admit_jit = self._admit_commit_dispatch
        self._bind_obs()

    def _bind_obs(self) -> None:
        """Bind this engine's labeled instruments against the registry
        installed RIGHT NOW (``repro.obs.metrics.install`` before
        construction). Every series carries a ``device`` label so a
        cluster fleet shares one registry. With the default (disabled)
        registry each update is a single attribute check — nothing
        allocates on the step path; the canonical name table lives in
        docs/ARCHITECTURE.md."""
        reg = obs_metrics.get_registry()
        self._mreg = reg
        c, g, h = reg.counter, reg.gauge, reg.histogram
        dl = ("device",)
        d = {"device": self.name}
        self._m_steps = c(
            "pam_engine_steps_total",
            "engine iterations (admission pass + decode step)",
            dl).labels(**d)
        self._m_decode_disp = c(
            "pam_engine_decode_dispatches_total",
            "fused decode device dispatches", dl).labels(**d)
        self._m_device_steps = c(
            "pam_engine_decode_device_steps_total",
            "decode steps executed on device (k per micro dispatch)",
            dl).labels(**d)
        self._m_prefill_disp = c(
            "pam_engine_prefill_dispatches_total",
            "prefill / suffix-prefill / chunk-slice dispatches",
            dl).labels(**d)
        self._m_admit_disp = c(
            "pam_engine_admit_dispatches_total",
            "donated admission-commit dispatches", dl).labels(**d)
        self._m_prefill_tokens = c(
            "pam_engine_prefill_tokens_total",
            "prompt tokens prefilled (novel only under prefix cache)",
            dl).labels(**d)
        self._m_decode_tokens = c(
            "pam_engine_decode_tokens_total",
            "decode tokens emitted to requests", dl).labels(**d)
        self._m_finished = c(
            "pam_engine_finished_total",
            "requests finished (EOS or budget)", dl).labels(**d)
        self._m_step_h = h(
            "pam_engine_step_seconds",
            "per-step latency (modeled or wall-clock)", dl).labels(**d)
        self._m_queue_wait = h(
            "pam_engine_queue_wait_seconds",
            "submit to admission, engine clock", dl).labels(**d)
        self._m_active = g(
            "pam_engine_active_slots",
            "slots decoding in the last step", dl).labels(**d)
        self._m_queue = g(
            "pam_engine_queue_depth",
            "requests waiting for admission", dl).labels(**d)
        self._m_pool = g(
            "pam_engine_pool_occupancy",
            "paged-pool block occupancy fraction", dl).labels(**d)
        tier_c = c("pam_engine_tier_read_tokens_total",
                   "participating tokens read, by KV tier",
                   ("device", "tier"))
        self._m_tier = tuple(tier_c.labels(device=self.name, tier=t)
                             for t in ("hot", "warm", "cold"))
        self._m_moved = c(
            "pam_engine_moved_tokens_total",
            "Alg. 2 tier migrations (tokens)", dl).labels(**d)
        self._m_blocks_touched = c(
            "pam_engine_blocks_touched_total",
            "pool pages touched by paged reads", dl).labels(**d)
        self._m_blocks_window = c(
            "pam_engine_blocks_window_total",
            "dense-window pages a full read would touch",
            dl).labels(**d)
        self._m_prefix_hits = c(
            "pam_engine_prefix_hits_total",
            "admissions that matched a cached prefix", dl).labels(**d)
        self._m_cached_prefix_tokens = c(
            "pam_engine_cached_prefix_tokens_total",
            "prefill compute skipped via prefix sharing (tokens)",
            dl).labels(**d)
        self._m_cow = c(
            "pam_engine_cow_copies_total",
            "copy-on-write tail-block duplications", dl).labels(**d)
        self._m_chunk_adm = c(
            "pam_engine_chunked_admissions_total",
            "admissions that went through chunked prefill",
            dl).labels(**d)
        self._m_chunk_slices = c(
            "pam_engine_chunk_slices_total",
            "chunked-prefill slice dispatches", dl).labels(**d)
        mig = c("pam_engine_migrations_total",
                "requests migrated (suspend/resume rides the same "
                "path)", ("device", "direction"))
        self._m_mig_in = mig.labels(device=self.name, direction="in")
        self._m_mig_out = mig.labels(device=self.name, direction="out")

    def _observe_step(self, stats: dict[str, Any], dt: float) -> None:
        """Per-step telemetry fan-out. Costs one ``enabled`` check when
        metrics are off plus one ``None`` check when tracing is off —
        the fused-dispatch fast path never allocates for telemetry."""
        if self._mreg.enabled:
            self._m_steps.inc()
            self._m_step_h.observe(dt)
            if stats["prefill_tokens"]:
                self._m_prefill_tokens.inc(stats["prefill_tokens"])
            self._m_active.set(stats["active"])
            self._m_queue.set(len(self.waiting))
            for m, v in zip(self._m_tier, stats["tier_reads"]):
                if v:
                    m.inc(int(v))
            if stats["moved_tokens"]:
                self._m_moved.inc(stats["moved_tokens"])
            if "blocks_touched" in stats:
                self._m_blocks_touched.inc(stats["blocks_touched"])
                self._m_blocks_window.inc(stats["blocks_window"])
            if self.allocator is not None:
                self._m_pool.set(self.allocator.occupancy)
        tr = obs_trace.COLLECTOR
        if tr is not None:
            tr.slice(self.name, "step", self.clock - dt, dt,
                     active=stats["active"],
                     prefill_tokens=stats["prefill_tokens"])
            tr.counter(self.name, "occupancy", self.clock,
                       active=stats["active"],
                       queue=len(self.waiting),
                       pool=(self.allocator.occupancy
                             if self.allocator is not None else 0.0))

    def _trace_finish(self, rs: RequestState) -> None:
        """Close a finished request's lifecycle track (finish instant +
        end of its open phase) and count it."""
        self._m_finished.inc()
        tr = obs_trace.COLLECTOR
        if tr is not None:
            rid = rs.request.id
            tr.mark(rid, "finish", self.clock, tokens=len(rs.outputs))
            phase = tr.open_phase(rid)
            if phase is not None:
                tr.end(rid, phase, self.clock)

    # ------------------------------------------------------------ builders
    def _get_micro(self, k: int):
        """Fused decode dispatch for ``k`` steps, from the shared cache."""
        if k not in self._micro_jits:
            self._micro_jits[k] = _fused_decode_fn(
                self.cfg, self.pam_cfg, self.scfg.max_len,
                self.scfg.max_batch, k, self.block_size, self.sentinel,
                self.scfg.temperature, self.scfg.top_k,
                self.scfg.eos_token, self.hot_window,
                self.scfg.sample_seed, self.mesh, self.cache_shardings)
        return self._micro_jits[k]

    def lower_decode_step(self, k: int = 1):
        """The fused ``k``-step decode dispatch lowered for this engine's
        current operands (a ``jax.stages.Lowered``: ``.compile()`` gives
        the program the device runs, ``.as_text()`` its HLO)."""
        B = self.scfg.max_batch
        return self._get_micro(k).lower(
            self.params, self.tokens_dev, self.cache, self.pam_state,
            jnp.zeros((B,), bool), jnp.asarray(self.rids_host))

    def _admit_commit_dispatch(self, cache, pam_state, tokens_dev, sub,
                               logits, slots, lengths, rids,
                               table_rows=None):
        """ONE donated device dispatch committing an admission group
        (resolved per group size from the shared compile cache)."""
        fn = _admit_commit_fn(self.pam_cfg, self.block_size,
                              int(slots.shape[0]), self.scfg.temperature,
                              self.scfg.top_k, self.hot_window,
                              self.scfg.sample_seed,
                              self.cache_shardings)
        args = (cache, pam_state, tokens_dev, sub, logits, slots, lengths,
                rids)
        if table_rows is not None:
            args += (table_rows,)
        return fn(*args)

    def _bucket_len(self, s_len: int) -> int:
        """Pow-2 prefill buckets cap the jit cache at O(log max_len)
        entries (SSM/hybrid running state can't absorb padding: exact)."""
        if (not self.scfg.bucket_prefill
                or self.cfg.family in ("ssm", "hybrid")):
            return s_len
        b = 1
        while b < s_len:
            b *= 2
        return min(b, self.scfg.max_len)

    def _prefill_for_len(self, bucket: int):
        if bucket not in self._prefill_jit:
            self._prefill_jit[bucket] = _prefill_fn(
                self.cfg, self.scfg.max_len,
                None if self.cache_shardings is None
                else self.cache_shardings.lengths)
        return self._prefill_jit[bucket]

    # ------------------------------------------------------------ lifecycle
    def submit(self, req: Request) -> None:
        self.requests[req.id] = RequestState(request=req,
                                             submit_clock=self.clock)
        self.waiting.append(req.id)
        tr = obs_trace.COLLECTOR
        if tr is not None:
            tr.begin(req.id, "queued", self.clock,
                     device=self.name, prompt=len(req.prompt))

    def _free_slots(self) -> list[int]:
        return [i for i, s in enumerate(self.slots) if s is None]

    def _reserve_fresh(self, need: int) -> None:
        """Best-effort headroom for ``need`` fresh blocks: under pool
        pressure, evict LRU trie-only cached prefixes (refcount 1 —
        nothing live maps them) until the free list covers the ask.
        Cache pressure degrades to recompute, never to failure; if live
        requests pin everything, the caller's ``allocate`` raises
        ``OutOfBlocks`` and normal admission backpressure applies."""
        if self.trie is not None and need > self.allocator.free_blocks:
            self.trie.evict(need - self.allocator.free_blocks)

    def _admit(self) -> int:
        """Prefill-priority admission (paper §4.2.3). Returns prompt
        tokens PROCESSED — with the prefix cache that is only each
        admission's novel suffix, so the latency model's admission cost
        scales with novel tokens, not prompt length. In paged mode each
        admission first claims pool blocks for its full window (prompt +
        budget); an exhausted pool leaves the request queued — capacity
        backpressure instead of failure.

        With ``prefix_cache`` the prompt is first split against the trie
        into cached-prefix + novel-suffix: the cached prefix's blocks
        are ADOPTED (refcount +1, zero prefill compute), a partially-
        covered tail block is pinned for copy-on-write, and only the
        suffix is prefilled (``_commit_trie``). Unmatched admissions
        flow through the unchanged group path below.

        Admissions sharing a prefill bucket are BATCHED: one bucket group
        = one prefill dispatch + one donated commit dispatch (scatter,
        pool fill, PAM placement and token seeds for every member), so a
        router burst of n same-length prompts costs 2 dispatches, not 2n.
        """
        with obs_trace.span("engine.admit"):
            # unified admission items: (rid, rs, prompt, s_len, slot,
            # table_row, start, cow_src) — start = cache-resident prefix
            # tokens (0 for plain admissions), cow_src = shared tail block
            # pinned for copy-on-write (-1 = none)
            admitted: list[tuple] = []
            free = self._free_slots()
            while self.waiting and free:
                rid = self.waiting.popleft()
                rs = self.requests[rid]
                prompt = np.asarray(rs.request.prompt, np.int32)
                s_len = len(prompt)
                if s_len + rs.request.max_new_tokens > self.scfg.max_len:
                    raise ValueError(f"request {rid} exceeds max_len")
                table_row = None
                matched, cow_src = 0, -1
                if self.allocator is not None:
                    window = s_len + rs.request.max_new_tokens
                    need = self.allocator.blocks_for(window)
                    if need > self.allocator.num_blocks:
                        # waiting would never help — fail loudly instead of
                        # starving this and every queued-behind request
                        raise ValueError(
                            f"request {rid} needs {need} blocks but the pool "
                            f"holds {self.allocator.num_blocks}")
                    shared: list[int] = []
                    if self.trie is not None:
                        # ≥ 1 token is always recomputed (the suffix prefill
                        # must produce first-token logits), so a full-prompt
                        # hit caps at s_len - 1
                        matched, ids = self.trie.lookup(prompt)
                        matched = min(matched, s_len - 1)
                        nfull = matched // self.block_size
                        shared = ids[:nfull]
                        if matched % self.block_size:
                            cow_src = ids[nfull]
                    try:
                        if shared:
                            # adopt first: the incref shields the matched
                            # blocks from the eviction pass below
                            self.allocator.adopt(rid, shared)
                        if cow_src >= 0:
                            self.allocator.incref(cow_src)  # CoW-source pin
                        self._reserve_fresh(need - len(shared))
                        self.allocator.allocate(rid, window)
                    except OutOfBlocks:
                        # roll back the adoption (decref) and the CoW pin,
                        # then wait for freed blocks
                        if cow_src >= 0:
                            self.allocator.decref(cow_src)
                        self.allocator.free(rid)
                        self.waiting.appendleft(rid)
                        break
                    table_row = self.allocator.padded_table(
                        rid, self.scfg.max_len // self.block_size,
                        self.sentinel)
                    self.peak_occupancy = max(self.peak_occupancy,
                                              self.allocator.occupancy)
                slot = free.pop(0)
                self._m_queue_wait.observe(self.clock - rs.submit_clock)
                if matched > 0:
                    self.prefix_hits += 1
                    self.cached_prefix_tokens += matched
                    self._m_prefix_hits.inc()
                    self._m_cached_prefix_tokens.inc(matched)
                if self.chunk and s_len - matched > self.chunk:
                    # chunked admission: claim the slot and the full
                    # block window NOW, then fill the prompt one bounded
                    # slice per engine step — interleaved with decode. The
                    # slot is occupied but NOT decode-eligible (PREFILLING)
                    # until the final slice's suffix commit seeds its first
                    # token.
                    rs.status, rs.slot = PREFILLING, slot
                    self.slots[slot] = rid
                    self.rids_host[slot] = rid
                    self._chunking[rid] = ChunkPlan(
                        rid=rid, slot=slot, start=matched, total=s_len,
                        budget=self.chunk, cow_src=cow_src)
                    self.chunked_admissions += 1
                    self._m_chunk_adm.inc()
                    tr = obs_trace.COLLECTOR
                    if tr is not None:
                        tr.begin(rid, "prefill", self.clock,
                                 device=self.name, novel=s_len - matched)
                    continue
                admitted.append((rid, rs, prompt, s_len, slot, table_row,
                                 matched, cow_src))

            # group by NOVEL-length prefill bucket, preserving admission
            # order. A group with any prefix-cache hit commits through the
            # batched suffix path (plain members ride along: their zeroed
            # prefix is masked inside attention — exact); prefix-free groups
            # keep the full-prefill path unchanged.
            groups: dict[int, list[tuple]] = {}
            for item in admitted:
                bucket = self._bucket_len(item[3] - item[6])
                groups.setdefault(bucket, []).append(item)
            return sum(
                self._commit_suffix_group(bucket, group)
                if any(it[6] > 0 for it in group)
                else self._commit_group(bucket, group)
                for bucket, group in groups.items())

    def _commit_group(self, bucket: int, group: list[tuple]) -> int:
        """Prefill + commit one same-bucket admission group: ONE batched
        prefill dispatch and ONE donated multi-slot commit dispatch."""
        n = len(group)
        padded = np.zeros((n, bucket), np.int32)
        lens = np.zeros((n,), np.int32)
        for i, (_, _, prompt, s_len, *_rest) in enumerate(group):
            padded[i, :s_len] = prompt
            lens[i] = s_len
        pre = self._prefill_for_len(bucket)
        with obs_trace.span("engine.prefill_dispatch"):
            logits, sub = pre(self.params, jnp.asarray(padded),
                              jnp.asarray(lens))
        self.prefill_dispatches += 1
        self._m_prefill_disp.inc()
        slots = np.array([g[4] for g in group], np.int32)
        rids = np.array([g[0] for g in group], np.uint32)
        args = (self.cache, self.pam_state, self.tokens_dev, sub, logits,
                jnp.asarray(slots), jnp.asarray(lens), jnp.asarray(rids))
        if self.allocator is not None:
            args += (jnp.asarray(np.stack([g[5] for g in group])),)
        with obs_trace.span("engine.commit_dispatch"):
            (self.cache, self.pam_state, self.tokens_dev,
             first_dev) = self._admit_jit(*args)
        self.admit_dispatches += 1
        self._m_admit_disp.inc()
        for rid, _, _, _, slot, *_rest in group:
            self.rids_host[slot] = rid
        if self.trie is not None:
            # publish AFTER the commit lands the prompts' KV in the pool
            # (and before any EOS teardown below frees the tables): the
            # trie takes its own refcount, so these prefixes stay cached
            # even after their publisher finishes
            self.novel_prefill_tokens += int(lens.sum())
            for rid, _, prompt, _, _, *_rest in group:
                self.trie.insert(prompt, self.allocator.table(rid))
        with obs_trace.span("engine.first_token_readback"):
            firsts = np.asarray(first_dev)
        for i, (rid, rs, _, _, slot, *_rest) in enumerate(group):
            self._finish_admit(rid, rs, slot, int(firsts[i]))
        return int(lens.sum())

    def _finish_admit(self, rid: int, rs: RequestState, slot: int,
                      tok: int) -> None:
        """Shared admission epilogue: record the first token and mark
        the request RUNNING — or DONE immediately when the PREFILL's
        token already ends it (EOS, or a max_new_tokens budget of 1).
        Such requests never join a decode wave (the fast path's
        _consume would otherwise skip them), so their times stamp
        here."""
        eos = self.scfg.eos_token
        rs.status, rs.slot = RUNNING, slot
        rs.outputs.append(tok)
        rs.planned = 1
        rs.first_token_time = None         # stamped after latency charge
        self.slots[slot] = rid
        self._m_decode_tokens.inc()
        tr = obs_trace.COLLECTOR
        if tr is not None:
            # begin() auto-closes the open queued/prefill phase
            tr.begin(rid, "decode", self.clock, device=self.name)
        if (eos >= 0 and tok == eos) or rs.request.max_new_tokens <= 1:
            rs.status = DONE
            rs.first_token_time = self.clock
            rs.token_times = [self.clock]
            rs.finish_time = self.clock
            self.slots[slot] = None
            if self.allocator is not None:
                self.allocator.free(rid)
            self._trace_finish(rs)

    def _suffix_coords(self, row: np.ndarray, start: int, t: int,
                       width: int) -> tuple[np.ndarray, np.ndarray]:
        """Token-granular pool scatter coordinates for ``width`` suffix
        positions beginning at absolute position ``start`` (``t`` of
        them real); padding past ``t`` routes to the sentinel trash
        block."""
        bs = self.block_size
        nb = self.scfg.max_len // bs
        pos = start + np.arange(width)
        bids = np.where(np.arange(width) < t,
                        row[np.minimum(pos // bs, nb - 1)],
                        self.sentinel).astype(np.int32)
        sids = (pos % bs).astype(np.int32)
        return bids, sids

    def _commit_suffix_group(self, bucket: int,
                             group: list[tuple]) -> int:
        """Prefill + commit one same-bucket admission group through the
        SUFFIX path: ONE batched suffix-prefill dispatch (each row's
        cached prefix gathered from the pool through its table — all
        zeros for plain riders) and ONE donated multi-slot commit
        dispatch (per-row CoW -> suffix scatter -> hot-row rebuild ->
        first-token sample -> PAM placement; ``_suffix_commit_fn``).
        Also commits FINAL chunked-prefill slices (``start`` = the last
        slice's begin; earlier slices already live in the pool).
        Returns the novel-token count — the group's actual prefill
        cost."""
        bs = self.block_size
        nb = self.scfg.max_len // bs
        n = len(group)
        padded = np.zeros((n, bucket), np.int32)
        suf_lens = np.zeros((n,), np.int32)
        starts = np.zeros((n,), np.int32)
        full_lens = np.zeros((n,), np.int32)
        rows = np.zeros((n, nb), np.int32)
        read_rows = np.zeros((n, nb), np.int32)
        bids = np.zeros((n, bucket), np.int32)
        sids = np.zeros((n, bucket), np.int32)
        cow_srcs = np.full((n,), self.sentinel, np.int32)
        cow_dsts = np.full((n,), self.sentinel, np.int32)
        cow_pins: list[int] = []
        for i, (rid, _, prompt, s_len, _, _, start, cow_src) \
                in enumerate(group):
            t = s_len - start
            padded[i, :t] = prompt[start:]
            suf_lens[i], starts[i], full_lens[i] = t, start, s_len
            row = self.allocator.padded_table(rid, nb, self.sentinel)
            rows[i] = row
            # READ view of the table for the prefix gather: a CoW row's
            # tail positions live in the publisher's cow_src until the
            # commit dispatch duplicates it — the prefill runs first,
            # so it must read through the source block
            read_rows[i] = row
            if cow_src >= 0:
                nfull = start // bs
                read_rows[i, nfull] = cow_src
                cow_srcs[i] = cow_src
                cow_dsts[i] = row[nfull]
                cow_pins.append(cow_src)
            bids[i], sids[i] = self._suffix_coords(row, start, t, bucket)
        pre = _suffix_prefill_fn(self.cfg, self.scfg.max_len,
                                 None if self.cache_shardings is None
                                 else self.cache_shardings.lengths)
        with obs_trace.span("engine.prefill_dispatch"):
            logits, suf_k, suf_v = pre(
                self.params, jnp.asarray(padded), self.cache.pk,
                self.cache.pv, jnp.asarray(read_rows),
                jnp.asarray(starts), jnp.asarray(suf_lens))
        self.prefill_dispatches += 1
        self._m_prefill_disp.inc()
        slots = np.array([g[4] for g in group], np.int32)
        rids = np.array([g[0] for g in group], np.uint32)
        fn = _suffix_commit_fn(self.pam_cfg, bs, n,
                               self.scfg.temperature, self.scfg.top_k,
                               self.hot_window, self.scfg.sample_seed,
                               self.cache_shardings)
        with obs_trace.span("engine.commit_dispatch"):
            (self.cache, self.pam_state, self.tokens_dev, first_dev) = fn(
                self.cache, self.pam_state, self.tokens_dev, suf_k, suf_v,
                logits, jnp.asarray(slots), jnp.asarray(full_lens),
                jnp.asarray(rids), jnp.asarray(rows), jnp.asarray(bids),
                jnp.asarray(sids), jnp.asarray(cow_srcs),
                jnp.asarray(cow_dsts))
        self.admit_dispatches += 1
        self._m_admit_disp.inc()
        for src in cow_pins:
            # the dispatch reading cow_src is enqueued; device ordering
            # makes any later reuse of the block safe — release the pin
            self.allocator.decref(src)
            self.cow_copies += 1
            self._m_cow.inc()
        if self.trie is not None:
            self.novel_prefill_tokens += int(suf_lens.sum())
        for rid, _, _, _, slot, *_rest in group:
            self.rids_host[slot] = rid
        if self.trie is not None:
            # publish AFTER the commit lands the suffix KV in the pool
            # and before any EOS teardown frees the tables
            for rid, _, prompt, _, _, *_rest in group:
                self.trie.insert(prompt, self.allocator.table(rid))
        with obs_trace.span("engine.first_token_readback"):
            firsts = np.asarray(first_dev)
        for i, (rid, rs, _, _, slot, *_rest) in enumerate(group):
            self._finish_admit(rid, rs, slot, int(firsts[i]))
        return int(suf_lens.sum())

    # --------------------------------------------- chunked prefill (PR 8)
    def _advance_chunks(self) -> int:
        """Advance every in-flight chunked admission by ONE slice (one
        fused dispatch each): intermediate slices scatter their KV into
        the pool (``_chunk_fill_fn``); the final slice commits through
        the batched suffix path, seeding the first token — the request
        turns RUNNING and joins the next decode wave. Returns prefill
        tokens processed (the latency model's admission charge), which
        never exceeds ``prefill_chunk`` per in-flight admission per
        step: that bound is what turns one monolithic prefill stall
        into evenly-spread slices."""
        if not self._chunking:
            return 0
        total = 0
        for rid in list(self._chunking):
            plan = self._chunking[rid]
            begin, t = plan.next_slice()
            final = begin + t >= plan.total
            rs = self.requests[rid]
            prompt = np.asarray(rs.request.prompt, np.int32)
            if final:
                del self._chunking[rid]
                # cow_src is -1 here by construction: a chunked plan
                # has >= 2 slices, so the first (CoW-carrying) slice
                # was an intermediate fill
                self._commit_suffix_group(
                    self._bucket_len(t),
                    [(rid, rs, prompt, plan.total, plan.slot, None,
                      begin, -1)])
            else:
                self._chunk_fill(plan, prompt, begin, t)
                plan.done += t
            plan.slices += 1
            self.chunk_slices += 1
            self._m_chunk_slices.inc()
            self.max_chunk_slice = max(self.max_chunk_slice, t)
            total += t
        return total

    def _chunk_fill(self, plan: ChunkPlan, prompt: np.ndarray,
                    begin: int, t: int) -> None:
        """One INTERMEDIATE slice: a single fused dispatch (optional
        first-slice CoW -> prefix gather -> suffix prefill over the
        slice -> pool scatter). Slices are always exactly ``budget``
        tokens, so this traces once per engine config."""
        nb = self.scfg.max_len // self.block_size
        bs = self.block_size
        row = self.allocator.padded_table(plan.rid, nb, self.sentinel)
        cow = plan.cow_src >= 0
        cow_dst = row[begin // bs] if cow else self.sentinel
        bids, sids = self._suffix_coords(row, begin, t, t)
        fn = _chunk_fill_fn(self.cfg, self.scfg.max_len, cow,
                            self.cache_shardings)
        with obs_trace.span("engine.prefill_dispatch"):
            self.cache = fn(
                self.params, self.cache,
                jnp.asarray(prompt[begin:begin + t][None]),
                jnp.asarray(row), jnp.int32(begin), jnp.int32(t),
                jnp.asarray(bids), jnp.asarray(sids),
                jnp.int32(max(plan.cow_src, 0)), jnp.int32(cow_dst))
        self.prefill_dispatches += 1
        self._m_prefill_disp.inc()
        if cow:
            self.allocator.decref(plan.cow_src)
            self.cow_copies += 1
            self._m_cow.inc()
            plan.cow_src = -1
        if self.trie is not None:
            self.novel_prefill_tokens += t

    # ------------------------------------------------------------ stepping
    def step(self) -> dict[str, Any]:
        """One engine iteration: admission (prefill) + one decode step for
        all running sequences — a single fused device dispatch. Returns
        step stats."""
        with obs_trace.span("engine.step"):
            t0 = time.perf_counter()
            prefill_tokens = self._admit() + self._advance_chunks()

            # decode-eligible = occupied AND past prefill (a chunking slot
            # is claimed but PREFILLING until its final slice commits)
            active_np = np.array([
                s is not None and self.requests[s].status == RUNNING
                for s in self.slots])
            stats: dict[str, Any] = {"prefill_tokens": prefill_tokens,
                                     "active": int(active_np.sum()),
                                     "tier_reads": np.zeros(3, np.int64),
                                     "moved_tokens": 0}
            if active_np.any():
                fused = self._get_micro(1)
                with obs_trace.span("engine.decode_dispatch"):
                    (self.tokens_dev, self.cache, self.pam_state,
                     bufs) = fused(
                        self.params, self.tokens_dev, self.cache,
                        self.pam_state, jnp.asarray(active_np),
                        jnp.asarray(self.rids_host))
                self.decode_dispatches += 1
                self.decode_device_steps += 1
                self._m_decode_disp.inc()
                self._m_device_steps.inc()
                with obs_trace.span("engine.readback"):
                    if self.mgr:
                        stats["tier_reads"] = np.asarray(
                            bufs.tier_reads[0], dtype=np.int64)
                        stats["hit_rate"] = float(bufs.hit_rate[0])
                        stats["moved_tokens"] = int(bufs.moved[0])
                    if self.block_size:
                        stats["blocks_touched"] = int(bufs.blocks[0, 0])
                        stats["blocks_window"] = int(bufs.blocks[0, 1])
                        stats["pool_occupancy"] = self.allocator.occupancy
                        self.blocks_touched_total += stats["blocks_touched"]
                        self.blocks_window_total += stats["blocks_window"]
                    stats["batch_lengths"] = np.asarray(bufs.lengths[0])
                    nxt = np.asarray(bufs.tokens[0])
                with obs_trace.span("engine.emit"):
                    self._emit_tokens(nxt, active_np)
            else:
                stats["batch_lengths"] = np.asarray(self.cache.lengths)

            # --- timing: modeled or wall-clock ----------------------------
            if self.latency_model is not None:
                dt = float(self.latency_model(stats))
            else:
                dt = time.perf_counter() - t0
            self.clock += dt
            if not prefill_tokens:
                # load signal: steady DECODE latency only — admission steps
                # carry a prefill spike that would whipsaw router/balancer
                # cost comparisons (prefill is priced separately there)
                self.last_step_time = dt
                self.last_step_stats = stats
            if active_np.any():
                self.busy_time += dt
            stats["step_time_s"] = dt
            self._stamp_times()
            self.steps += 1
            self._observe_step(stats, dt)
            return stats

    def _emit_tokens(self, nxt: np.ndarray, active: np.ndarray) -> None:
        for slot, rid in enumerate(self.slots):
            if rid is None or not active[slot]:
                continue
            rs = self.requests[rid]
            tok = int(nxt[slot])
            rs.outputs.append(tok)
            self._m_decode_tokens.inc()
            rs.planned = len(rs.outputs)
            done = (len(rs.outputs) >= rs.request.max_new_tokens
                    or tok == self.scfg.eos_token)
            if done:
                rs.status = DONE
                rs.finish_time = None  # stamped in _stamp_times
                self.slots[slot] = None
                if self.allocator is not None:
                    self.allocator.free(rid)   # blocks recycle; the next
                    # owner overwrites them at prefill commit

    def _stamp_times(self) -> None:
        for rs in self.requests.values():
            if rs.status in (RUNNING, DONE):
                if rs.first_token_time is None:
                    rs.first_token_time = self.clock
                if len(rs.token_times) < len(rs.outputs):
                    rs.token_times += [self.clock] * (
                        len(rs.outputs) - len(rs.token_times))
                if rs.status == DONE and rs.finish_time is None:
                    rs.finish_time = self.clock
                    self._trace_finish(rs)

    def run(self, max_steps: int = 10_000) -> dict[str, Any]:
        """Run until all submitted requests finish. Returns summary."""
        if self.scfg.micro_steps > 1:
            return self._run_fast(max_steps)
        for _ in range(max_steps):
            if not self.waiting and all(s is None for s in self.slots):
                break
            self.step()
        return self.summary()

    # ------------------------------------------------- pipelined fast path
    def _run_fast(self, max_steps: int) -> dict[str, Any]:
        """Multi-step fused micro-loop. With ``eos_token == -1`` the loop
        is PIPELINED: the host consumes step *t-1*'s token/stat buffers
        while step *t* runs on device, and request lifecycle (doneness,
        slot frees, admission) advances from *planned* token counts —
        known without reading token values.

        With ``eos_token >= 0`` the micro-loop still fuses k device steps
        per dispatch (EOS detection runs ON DEVICE: a slot that samples
        EOS freezes for the remaining micro-steps), but each dispatch's
        buffers are consumed synchronously so EOS completions free their
        slot before the next admission pass."""
        micro = self.scfg.micro_steps
        pipelined = self.scfg.eos_token < 0
        pending: Optional[tuple] = None
        self._wall_anchor = time.perf_counter()
        while self.steps < max_steps:
            if not self.waiting and all(s is None for s in self.slots):
                break
            prefill_tokens = self._admit() + self._advance_chunks()
            pairs = [(i, rid) for i, rid in enumerate(self.slots)
                     if rid is not None
                     and self.requests[rid].status == RUNNING]
            if not pairs:
                if self._chunking:
                    # chunk slices are filling with nothing decoding:
                    # charge the admission latency directly (there is
                    # no decode dispatch to carry it) so TTFT stays
                    # honest in micro mode
                    self._charge_prefill_only(prefill_tokens)
                if prefill_tokens:
                    continue   # the whole admission wave finished at
                    # prefill (EOS / 1-token budgets); admit the rest
                break   # nothing runnable (all waiting requests invalid)
            remaining = min(self.requests[rid].request.max_new_tokens
                            - self.requests[rid].planned
                            for _, rid in pairs)
            k = 1       # largest pow-2 micro-count no request overshoots
            while k * 2 <= min(remaining, micro):
                k *= 2
            active_np = np.zeros((self.scfg.max_batch,), bool)
            for slot, _ in pairs:
                active_np[slot] = True
            fused = self._get_micro(k)
            with obs_trace.span("engine.decode_dispatch"):
                (self.tokens_dev, self.cache, self.pam_state,
                 bufs) = fused(
                    self.params, self.tokens_dev, self.cache,
                    self.pam_state, jnp.asarray(active_np),
                    jnp.asarray(self.rids_host))
            self.decode_dispatches += 1
            self.decode_device_steps += k
            self._m_decode_disp.inc()
            self._m_device_steps.inc(k)
            self.steps += k
            rec = (bufs, pairs, k, prefill_tokens)
            if pipelined:
                # advance lifecycle from planned counts — no token readback
                for slot, rid in pairs:
                    rs = self.requests[rid]
                    rs.planned += k
                    if rs.planned >= rs.request.max_new_tokens:
                        rs.status = DONE
                        self.slots[slot] = None
                        if self.allocator is not None:
                            self.allocator.free(rid)
                if pending is not None:
                    self._consume(pending)  # overlaps with this dispatch
                pending = rec
            else:
                self._consume(rec)          # EOS needs the token values
        if pending is not None:
            self._consume(pending)
        return self.summary()

    def _consume(self, rec: tuple) -> None:
        """Drain one dispatch's StepBufs: append token values, charge the
        latency model per fused sub-step, stamp times. In EOS mode this
        also drives the lifecycle: the first EOS (or the max_new_tokens
        boundary) marks the request DONE and frees its slot and blocks —
        post-EOS micro-steps were frozen on device and are skipped."""
        bufs, pairs, k, prefill_tokens = rec
        eos = self.scfg.eos_token
        with obs_trace.span("engine.readback"):
            toks = np.asarray(bufs.tokens)          # blocks until done
            reads = np.asarray(bufs.tier_reads, dtype=np.int64)
            moved = np.asarray(bufs.moved)
            lens = np.asarray(bufs.lengths)
            hits = np.asarray(bufs.hit_rate)
            if self.block_size:
                blocks = np.asarray(bufs.blocks)
                self.blocks_touched_total += int(blocks[:, 0].sum())
                self.blocks_window_total += int(blocks[:, 1].sum())
        if self.latency_model is None:
            wall = time.perf_counter()
            dt_wall = (wall - self._wall_anchor) / k
            self._wall_anchor = wall
        for j in range(k):
            stats = {"prefill_tokens": prefill_tokens if j == 0 else 0,
                     "active": len(pairs), "tier_reads": reads[j],
                     "moved_tokens": int(moved[j]),
                     "batch_lengths": lens[j]}
            if self.mgr:
                stats["hit_rate"] = float(hits[j])
            dt = (float(self.latency_model(stats))
                  if self.latency_model is not None else dt_wall)
            self.clock += dt
            if not stats["prefill_tokens"]:
                self.last_step_time = dt     # decode-only load signal
                self.last_step_stats = stats
            self.busy_time += dt
            self._observe_step(stats, dt)
            for slot, rid in pairs:
                rs = self.requests[rid]
                if eos >= 0 and rs.status == DONE:
                    continue                 # froze at EOS mid-dispatch
                tok = int(toks[j, slot])
                rs.outputs.append(tok)
                self._m_decode_tokens.inc()
                rs.planned = max(rs.planned, len(rs.outputs))
                if rs.first_token_time is None:
                    rs.first_token_time = self.clock
                while len(rs.token_times) < len(rs.outputs):
                    rs.token_times.append(self.clock)
                done = (len(rs.outputs) >= rs.request.max_new_tokens
                        or (eos >= 0 and tok == eos))
                if done and rs.finish_time is None:
                    rs.finish_time = self.clock
                    self._trace_finish(rs)
                if done and rs.status != DONE:
                    rs.status = DONE
                    if eos >= 0:             # EOS mode frees slots here
                        self.slots[slot] = None
                        if self.allocator is not None:
                            self.allocator.free(rid)

    def _charge_prefill_only(self, prefill_tokens: int) -> None:
        """Clock charge for a fast-path iteration that did admission/
        chunk-fill work but dispatched no decode step (nothing RUNNING
        yet). Only the chunked path takes it — legacy admission waves
        keep their PR 1 timing behavior bit-for-bit."""
        stats = {"prefill_tokens": prefill_tokens, "active": 0,
                 "tier_reads": np.zeros(3, np.int64), "moved_tokens": 0,
                 "batch_lengths": np.asarray(self.cache.lengths)}
        if self.latency_model is not None:
            self.clock += float(self.latency_model(stats))
        else:
            wall = time.perf_counter()
            self.clock += wall - self._wall_anchor
            self._wall_anchor = wall

    # ------------------------------------------ cluster / migration hooks
    def can_accept(self, n_tokens: int, *,
                   reserve_queued: bool = True) -> bool:
        """True iff a request with an ``n_tokens`` window (prompt +
        generation budget) could be admitted RIGHT NOW: a free slot and,
        in paged mode, enough free pool blocks.

        With ``reserve_queued`` (default) both are counted NET of the
        engine's own waiting queue — requests already bound here but not
        yet prefilled — so a router's dispatch round cannot over-assign
        a device. Migration rescues pass ``reserve_queued=False`` on
        purpose: pulling a straggler off a slow device is allowed to
        compete with queued admissions for slots/blocks (shortage
        degrades to admission backpressure, never failure), which beats
        strict admission order when the alternative is the straggler
        finishing on a device several times slower."""
        queued_slots = len(self.waiting) if reserve_queued else 0
        if len(self._free_slots()) - queued_slots < 1:
            return False
        if self.allocator is None:
            return True
        queued = sum(
            self.allocator.blocks_for(
                len(self.requests[rid].request.prompt)
                + self.requests[rid].request.max_new_tokens)
            for rid in self.waiting) if reserve_queued else 0
        return (self.allocator.blocks_for(n_tokens)
                <= self.allocator.free_blocks - queued)

    def serviceable(self, n_tokens: int) -> bool:
        """True iff an ``n_tokens`` window fits this device at all
        (``max_len`` and total pool size) — the admission feasibility
        check routers use before assigning a request."""
        if n_tokens > self.scfg.max_len:
            return False
        if self.allocator is None:
            return True
        return self.allocator.blocks_for(n_tokens) <= self.allocator.num_blocks

    def load_signal(self) -> dict[str, Any]:
        """Host-visible load snapshot for routers/balancers: queue depth,
        running count, modeled last-step latency and pool occupancy —
        the paper's inter-device scheduling cost signal (§4.3)."""
        running = sum(s is not None for s in self.slots)
        return {
            "queue_depth": len(self.waiting),
            "running": running,
            "free_slots": self.scfg.max_batch - running,
            "step_time_s": self.last_step_time,
            "pool_occupancy": (self.allocator.occupancy
                               if self.allocator is not None else 0.0),
            "free_blocks": (self.allocator.free_blocks
                            if self.allocator is not None else -1),
            "clock": self.clock,
        }

    def slot_importance_mass(self) -> dict[int, float]:
        """Per running request: total importance mass (sum of the eq. 7
        EMA over its tokens) — the balancer's migration-victim signal
        (move the LOWEST mass first: cheapest accuracy stake)."""
        running = [(slot, rid) for slot, rid in enumerate(self.slots)
                   if rid is not None
                   and self.requests[rid].status == RUNNING]
        if self.pam_cfg is None:
            return {rid: 0.0 for _, rid in running}
        mass = np.asarray(jnp.sum(self.pam_state.importance, axis=-1))
        return {rid: float(mass[slot]) for slot, rid in running}

    def _require_migratable(self) -> None:
        if self.cache.k.size == 0 or self.cache.conv.size > 0 \
                or self.cache.ckv.size > 0:
            raise ValueError(f"{self.cfg.name}: KV migration requires a "
                             f"pure GQA decode cache")

    def export_request(self, rid: int) -> dict[str, Any]:
        """Export a RUNNING request for inter-device migration: gather
        its KV into the portable logical layout (hot tokens from the
        dense cache, warm/cold through the block table — the §6.2 sender
        side), copy its PAM rows and host bookkeeping, then free the slot
        and pool blocks WITHOUT finishing the request. Returns the
        snapshot dict consumed by ``import_request`` (see
        ``repro.cluster.migration.KVSnapshot``)."""
        self._require_migratable()
        rs = self.requests.get(rid)
        if rs is None or rs.status != RUNNING:
            raise ValueError(f"request {rid} is not running here")
        slot = rs.slot
        nb = self.scfg.max_len // self.block_size if self.block_size else 0
        table_row = (jnp.asarray(self.allocator.padded_table(
            rid, nb, self.sentinel)) if self.allocator is not None
            else jnp.zeros((0,), jnp.int32))
        tier_row = (self.pam_state.tier[slot] if self.pam_cfg is not None
                    else jnp.zeros((self.scfg.max_len,), jnp.int32))
        k_row, v_row = _export_gather_fn(self.block_size, self.hot_window)(
            self.cache.k, self.cache.v, self.cache.pk, self.cache.pv,
            table_row, tier_row, jnp.int32(slot),
            self.cache.lengths[slot])
        snap = {
            "request": rs.request,
            "outputs": list(rs.outputs),
            "planned": len(rs.outputs),
            "length": int(np.asarray(self.cache.lengths[slot])),
            "token": int(np.asarray(self.tokens_dev[slot])),
            "k": np.asarray(k_row),
            "v": np.asarray(v_row),
            "importance": (np.asarray(self.pam_state.importance[slot])
                           if self.pam_cfg is not None else None),
            "tier": (np.asarray(tier_row)
                     if self.pam_cfg is not None else None),
            "last_hot": (np.asarray(self.pam_state.last_hot[slot])
                         if self.pam_cfg is not None else None),
            "first_token_time": rs.first_token_time,
            "token_times": list(rs.token_times),
            "arrival": rs.request.arrival,
            "src": self.name,
        }
        # free-without-finish: the slot recycles and the request's
        # reference on each block DECREFS — with prefix sharing, blocks
        # another live request or the trie also maps survive the export
        # untouched (their bytes stay valid for every remaining sharer);
        # the migrating request's only live copy is now the snapshot
        self.slots[slot] = None
        if self.allocator is not None:
            self.allocator.free(rid)
        del self.requests[rid]
        self.migrations_out += 1
        self._m_mig_out.inc()
        tr = obs_trace.COLLECTOR
        if tr is not None:
            tr.mark(rid, "migrate_out", self.clock, src=self.name)
            tr.begin(rid, "suspended", self.clock)  # closes "decode"
        return snap

    def import_request(self, snap: dict[str, Any]) -> None:
        """Admit a migrated request mid-decode (§6.2 receiver side): ONE
        donated dispatch installs the snapshot KV into a free slot (and
        through a freshly-allocated block table in paged mode), inserts
        the PAM rows and seeds the device token vector; decode resumes
        exactly where the source stopped. Raises ``OutOfBlocks`` /
        ``ValueError`` when this device cannot take the request — check
        ``can_accept`` first."""
        self._require_migratable()
        req: Request = snap["request"]
        free = self._free_slots()
        if not free:
            raise ValueError(f"{self.name}: no free slot for migrated "
                             f"request {req.id}")
        if snap["k"].shape[2] != self.scfg.max_len:
            raise ValueError("snapshot window does not match max_len "
                             f"({snap['k'].shape[2]} vs {self.scfg.max_len})")
        window = len(req.prompt) + req.max_new_tokens
        table_row = None
        if self.allocator is not None:
            # physical ids never travel: the import always allocates
            # fresh blocks here (no cross-device sharing); trie-only
            # cached prefixes yield first under pressure
            self._reserve_fresh(self.allocator.blocks_for(window))
            self.allocator.allocate(req.id, window)   # may raise OutOfBlocks
            table_row = self.allocator.padded_table(
                req.id, self.scfg.max_len // self.block_size, self.sentinel)
            self.peak_occupancy = max(self.peak_occupancy,
                                      self.allocator.occupancy)
        slot = free[0]
        Smax = self.scfg.max_len
        imp = (snap["importance"] if snap["importance"] is not None
               else np.zeros((Smax,), np.float32))
        tier = (snap["tier"] if snap["tier"] is not None
                else np.zeros((Smax,), np.int32))
        lh = (snap["last_hot"] if snap["last_hot"] is not None
              else np.zeros((Smax,), bool))
        args = (self.cache, self.pam_state, self.tokens_dev,
                jnp.asarray(snap["k"]), jnp.asarray(snap["v"]),
                jnp.asarray(imp), jnp.asarray(tier), jnp.asarray(lh),
                jnp.int32(slot), jnp.int32(snap["length"]),
                jnp.int32(snap["token"]))
        if table_row is not None:
            args += (jnp.asarray(table_row),)
        fn = _import_commit_fn(self.pam_cfg is not None, self.block_size,
                               self.hot_window, self.cache_shardings)
        self.cache, self.pam_state, self.tokens_dev = fn(*args)
        rs = RequestState(
            request=req, status=RUNNING, slot=slot,
            outputs=list(snap["outputs"]), planned=snap["planned"],
            first_token_time=snap["first_token_time"],
            token_times=list(snap["token_times"]))
        self.requests[req.id] = rs
        self.slots[slot] = req.id
        self.rids_host[slot] = req.id
        if self.trie is not None:
            # the imported row holds the prompt's KV at its prompt
            # positions — publish it so later arrivals share it here too
            self.trie.insert(np.asarray(req.prompt, np.int32),
                             self.allocator.table(req.id))
        self.migrations_in += 1
        self._m_mig_in.inc()
        tr = obs_trace.COLLECTOR
        if tr is not None:
            tr.mark(req.id, "migrate_in", self.clock, dst=self.name)
            tr.begin(req.id, "decode", self.clock, device=self.name)

    # ----------------------------------------- suspend / resume (recovery)
    def suspend_request(self, rid: int) -> dict[str, Any]:
        """Preemption-by-demotion hook: detach a RUNNING request into a
        host-held snapshot, freeing its slot and pool blocks for a more
        urgent admission. The snapshot is ``export_request``'s portable
        dict — resuming it later (here or on any compatible engine) via
        ``resume_request`` continues the stream bit-exactly, because the
        per-request sampling keys depend only on (seed, rid, position)."""
        return self.export_request(rid)

    def resume_request(self, snap: dict[str, Any]) -> None:
        """Re-admit a suspended request (one donated dispatch); the twin
        of ``suspend_request``. Raises ``OutOfBlocks``/``ValueError``
        when capacity is still short — check ``can_accept`` first."""
        self.import_request(snap)

    # ----------------------------------------------- unified serving surface
    def as_router(self, *, preemptible: bool = False):
        """This engine wrapped as a one-device ``ClusterRouter`` — the
        single backend shape every serving surface (CLI, async server,
        benchmarks) drives since PR 10. Scheduling stays a no-op with
        one device; the router contributes admission, eventing and the
        ``serve`` generator."""
        from repro.cluster.router import ClusterRouter
        return ClusterRouter.for_engine(self, preemptible=preemptible)

    def serve(self, requests: Optional[Iterable[Request]] = None, *,
              max_ticks: Optional[int] = None) -> Iterator[Any]:
        """Unified streaming surface: submit ``requests`` (if given) and
        yield ``ServeEvent``s until everything drains. Identical shape
        on a bare engine and on a cluster (``ClusterRouter.serve``)."""
        yield from self.as_router().serve(requests, max_ticks=max_ticks)

    def params_bytes_per_device(self) -> int:
        """Bytes of model params RESIDENT PER DEVICE. Unsharded engines
        hold the full tree; a shard-``s`` replica group holds one
        GSPMD-sharded copy, so this is ~1/s of the total (replicated
        leaves — norms, biases — keep full size)."""
        total = 0
        for leaf in jax.tree.leaves(self.params):
            shape = getattr(leaf, "shape", ())
            shd = getattr(leaf, "sharding", None)
            if shd is not None and hasattr(shd, "shard_shape"):
                shape = shd.shard_shape(shape)
            n = 1
            for d in shape:
                n *= d
            total += n * getattr(leaf, "dtype", np.dtype(np.float32)
                                 ).itemsize
        return total

    # ------------------------------------------------------------ metrics
    def summary(self) -> dict[str, Any]:
        """Run metrics: throughput, TPOT percentiles, dispatch counts; in
        paged mode also pages-touched vs dense-window-pages per step (the
        sparse-read win) and pool occupancy."""
        done = [r for r in self.requests.values() if r.status == DONE]
        total_tokens = sum(len(r.outputs) for r in done)
        tpots = []
        for r in done:
            if len(r.token_times) > 1:
                gaps = np.diff(r.token_times)
                tpots.extend(gaps.tolist())
        out = {
            "finished": len(done),
            "total_tokens": total_tokens,
            "sim_time_s": self.clock,
            "throughput_tok_s": total_tokens / max(self.clock, 1e-9),
            "p50_tpot_s": float(np.percentile(tpots, 50)) if tpots else 0.0,
            "p99_tpot_s": float(np.percentile(tpots, 99)) if tpots else 0.0,
            "steps": self.steps,
            "decode_dispatches": self.decode_dispatches,
            "decode_device_steps": self.decode_device_steps,
            "prefill_dispatches": self.prefill_dispatches,
            "admit_dispatches": self.admit_dispatches,
            "migrations_in": self.migrations_in,
            "migrations_out": self.migrations_out,
        }
        if self.shard > 1:
            out["shard"] = self.shard
            out["param_bytes_per_device"] = self.params_bytes_per_device()
        if self.block_size:
            n = max(self.decode_device_steps, 1)
            out["blocks_touched_per_step"] = self.blocks_touched_total / n
            out["blocks_window_per_step"] = self.blocks_window_total / n
            out["pool_occupancy_peak"] = self.peak_occupancy
            out["pool_occupancy_now"] = self.allocator.occupancy
            # hot-tier footprint: ring slots x KV bytes, per batch slot —
            # independent of max_len once hot_window is set (PR 5)
            out["hot_window"] = self.hot_window or self.scfg.max_len
            out["hot_bytes_per_slot"] = int(
                (self.cache.k.nbytes + self.cache.v.nbytes)
                // self.scfg.max_batch)
        if self.chunk:
            out["chunked_admissions"] = self.chunked_admissions
            out["chunk_slices"] = self.chunk_slices
            out["max_chunk_slice_tokens"] = self.max_chunk_slice
        if self.trie is not None:
            out["prefix_hits"] = self.prefix_hits
            out["cached_prefix_tokens"] = self.cached_prefix_tokens
            out["novel_prefill_tokens"] = self.novel_prefill_tokens
            out["cow_copies"] = self.cow_copies
            out["trie_blocks"] = self.trie.num_blocks
            out["trie_evictions"] = self.trie.evictions
        return out

    def slo_attainment(self, slo_s: float) -> float:
        """Fraction of decode-token gaps within the SLO (paper Fig. 9)."""
        gaps = []
        for r in self.requests.values():
            if len(r.token_times) > 1:
                gaps.extend(np.diff(r.token_times).tolist())
        if not gaps:
            return 1.0
        return float(np.mean(np.asarray(gaps) <= slo_s))


# Public alias matching the paper's naming.
PAMEngine = ServingEngine
