"""Paged warm/cold KV pool + in-kernel block-table gather (PR 2).

Covers the acceptance surface: dense-vs-paged decode equivalence through
the real serving engine, the Pallas kernel's table walk against the jnp
reference gather, allocator reuse-after-free / no-double-mapping,
migration-as-table-edit preserving attention output, the one-fused-
dispatch-per-step invariant with block tables, and the sparse-read
accounting (pages touched < dense-window pages).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import pam_interface, tiers
from repro.core.tiers import COLD, HOT, WARM
from repro.kernels import ops as kops
from conftest import build_model, make_pam

from repro.models import transformer as tf
from repro.serving import (BlockAllocator, EngineSpec, OutOfBlocks,
                           PAMManagerConfig, Request, ServingConfig)

jax.config.update("jax_platform_name", "cpu")


# ----------------------------------------------------------- kernel / ops
def _rand_pool(key, NB, bs, Hkv, d):
    pk = jax.random.normal(jax.random.fold_in(key, 1), (NB + 1, bs, Hkv, d))
    pv = jax.random.normal(jax.random.fold_in(key, 2), (NB + 1, bs, Hkv, d))
    return pk, pv


@pytest.mark.parametrize("rep", [1, 4])
@pytest.mark.parametrize("bs", [8, 16, 48])     # 48: two mask words
def test_paged_kernel_matches_reference_gather(rep, bs):
    """flash_decode_paged (interpret mode, block table walked in-grid,
    all kv heads of a block per grid cell, mask as scalar-prefetched bit
    words) equals the jnp gather-through-table reference partial."""
    B, Hkv, d, NB, nb = 3, 2, 16, 12, 4
    H = Hkv * rep
    key = jax.random.PRNGKey(rep * 31 + bs)
    q = jax.random.normal(jax.random.fold_in(key, 0), (B, H, d))
    pk, pv = _rand_pool(key, NB, bs, Hkv, d)
    bt = jax.random.randint(jax.random.fold_in(key, 3), (B, nb), 0, NB)
    mask = jax.random.uniform(jax.random.fold_in(key, 4),
                              (B, nb * bs)) < 0.4
    got = kops.paged_decode_attention_partial(q, pk, pv, bt, mask,
                                              use_kernel=True,
                                              interpret=True)
    ref = kops.paged_decode_attention_partial(q, pk, pv, bt, mask,
                                              use_kernel=False)
    for a, b in zip(got, ref):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_paged_kernel_dead_rows_and_shard_offset(dtype):
    """A row with no participating token is the merge identity (m=-inf,
    l=0, o=0) on the kernel path as on the reference; with
    ``block_offset`` both read only the local block range; bf16 pools
    agree at the fp32 accumulation tolerance."""
    B, Hkv, rep, d, NB, nb, bs = 3, 2, 2, 16, 12, 4, 16
    key = jax.random.PRNGKey(5)
    q = jax.random.normal(jax.random.fold_in(key, 0), (B, Hkv * rep, d),
                          dtype)
    pk, pv = (x.astype(dtype) for x in _rand_pool(key, NB, bs, Hkv, d))
    bt = jax.random.randint(jax.random.fold_in(key, 3), (B, nb), 0, NB)
    mask = jax.random.uniform(jax.random.fold_in(key, 4),
                              (B, nb * bs)) < 0.5
    mask = mask.at[1].set(False)
    for offset in (None, 4):
        local = (pk, pv) if offset is None else (pk[4:9], pv[4:9])
        got = kops.paged_decode_attention_partial(
            q, *local, bt, mask, block_offset=offset, use_kernel=True,
            interpret=True)
        ref = kops.paged_decode_attention_partial(
            q, *local, bt, mask, block_offset=offset, use_kernel=False)
        assert np.isneginf(np.asarray(got.m[1])).all()
        assert not np.asarray(got.l[1]).any()
        assert not np.asarray(got.o[1]).any()
        for a, b in zip(got, ref):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=1e-5, atol=1e-5)


def _mirrored_pool(kc, vc, bs):
    """Build a pool + disjoint per-sequence tables mirroring a dense
    (B, Hkv, S, d) cache, S a block multiple."""
    B, Hkv, S, d = kc.shape
    nb = S // bs
    table = (jnp.arange(nb)[None, :] + jnp.arange(B)[:, None] * nb)
    pool_k = jnp.zeros((B * nb + 1, bs, Hkv, d)).at[:B * nb].set(
        jnp.moveaxis(kc, 1, 2).reshape(B * nb, bs, Hkv, d))
    pool_v = jnp.zeros((B * nb + 1, bs, Hkv, d)).at[:B * nb].set(
        jnp.moveaxis(vc, 1, 2).reshape(B * nb, bs, Hkv, d))
    return pool_k, pool_v, table.astype(jnp.int32)


def test_paged_tiered_attention_equals_dense_masked():
    """Hot(dense) ⊕ paged(pool) merged partials == one masked softmax
    over the union participation set — for any tier split."""
    B, H, Hkv, d, S, bs = 3, 8, 2, 16, 32, 8
    key = jax.random.PRNGKey(7)
    q = jax.random.normal(jax.random.fold_in(key, 0), (B, H, d))
    kc = jax.random.normal(jax.random.fold_in(key, 1), (B, Hkv, S, d))
    vc = jax.random.normal(jax.random.fold_in(key, 2), (B, Hkv, S, d))
    pool_k, pool_v, table = _mirrored_pool(kc, vc, bs)
    lens = jnp.array([32, 20, 9])
    live = jnp.arange(S)[None, :] < lens[:, None]
    part = jax.random.uniform(jax.random.fold_in(key, 3), (B, S)) < 0.7
    hot = jax.random.uniform(jax.random.fold_in(key, 4), (B, S)) < 0.5
    hot_m = hot & part & live
    pgd_m = ~hot & part & live
    out_p, mass_p = kops.paged_masked_decode_attention(
        q, kc, vc, pool_k, pool_v, table, hot_m, pgd_m, lens,
        use_kernel=False)
    out_d, mass_d = kops.masked_decode_attention(q, kc, vc, part, lens,
                                                 use_kernel=False)
    np.testing.assert_allclose(np.asarray(out_p), np.asarray(out_d),
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(mass_p), np.asarray(mass_d),
                               rtol=1e-4, atol=1e-5)


def test_migration_is_a_table_edit():
    """Alg. 2 tier moves re-tag tokens; with a shared pool NO pool bytes
    change and the merged attention output is invariant to the split."""
    B, H, Hkv, d, S, bs = 2, 4, 2, 16, 32, 8
    key = jax.random.PRNGKey(11)
    q = jax.random.normal(jax.random.fold_in(key, 0), (B, H, d))
    kc = jax.random.normal(jax.random.fold_in(key, 1), (B, Hkv, S, d))
    vc = jax.random.normal(jax.random.fold_in(key, 2), (B, Hkv, S, d))
    pool_k, pool_v, table = _mirrored_pool(kc, vc, bs)
    lens = jnp.full((B,), S)
    part = jax.random.uniform(jax.random.fold_in(key, 3), (B, S)) < 0.6

    tier = jax.random.randint(jax.random.fold_in(key, 4), (B, S), 0, 3)
    moved = jax.random.uniform(jax.random.fold_in(key, 5), (B, S)) < 0.3
    tier2 = pam_interface.migrate_tier_tags(tier, moved, WARM)
    assert int(jnp.sum(tier2 != tier)) > 0     # something migrated

    outs = []
    for t in (tier, tier2):
        hot_m = part & (t == HOT)
        pgd_m = part & (t != HOT)
        out, _ = kops.paged_masked_decode_attention(
            q, kc, vc, pool_k, pool_v, table, hot_m, pgd_m, lens,
            use_kernel=False)
        outs.append(np.asarray(out))
    np.testing.assert_allclose(outs[0], outs[1], rtol=1e-5, atol=1e-5)


def test_block_residency_summary():
    tier = jnp.array([[HOT, HOT, WARM, WARM, COLD, COLD, COLD, COLD]])
    valid = jnp.array([[True] * 6 + [False] * 2])
    res = tiers.block_residency(tier, valid, 4)
    np.testing.assert_array_equal(np.asarray(res), [[HOT, COLD]])
    counts = tiers.blocks_per_tier(tier, valid, 4)
    assert int(counts[HOT]) == 1 and int(counts[COLD]) == 1


# -------------------------------------------------------------- allocator
def test_allocator_reuse_after_free():
    alloc = BlockAllocator(num_blocks=8, block_size=4)
    t0 = list(alloc.allocate(0, 16))           # 4 blocks
    t1 = list(alloc.allocate(1, 16))           # 4 blocks — pool full
    assert alloc.check_no_double_mapping()
    with pytest.raises(OutOfBlocks):
        alloc.allocate(2, 4)
    alloc.free(0)
    t2 = list(alloc.allocate(2, 16))
    assert set(t2) == set(t0)                  # physical ids recycled
    assert alloc.check_no_double_mapping()
    assert not (set(t2) & set(t1))
    row = alloc.padded_table(2, 8, sentinel=8)
    assert row.shape == (8,)
    assert list(row[4:]) == [8] * 4            # unmapped -> sentinel


# ---------------------------------------------------------- serving engine
def _engine(block_size=0, pool_blocks=None, micro_steps=1, max_batch=3,
            max_len=64, hot=4, warm=8, seed=0):
    cfg, params = build_model("qwen3-0.6b", seed=seed)
    pam = make_pam(max_len=max_len, hot=hot, warm=warm, recency_window=2)
    return cfg, EngineSpec(model=cfg, serving=ServingConfig(
        max_batch=max_batch, max_len=max_len, pam=pam,
        micro_steps=micro_steps, block_size=block_size,
        pool_blocks=pool_blocks)).build(params)


def _submit(cfg, eng, n=4, plen=30, max_new=10, seed=0):
    rng = np.random.default_rng(seed)
    for i in range(n):
        eng.submit(Request(id=i, prompt=rng.integers(0, cfg.vocab, plen),
                           max_new_tokens=max_new))


def test_paged_engine_tokens_match_dense_engine():
    """The paged block-table decode path emits the same greedy tokens as
    the dense path — storage layout, not math. Long prompts + tiny hot
    capacity force real warm/cold (paged) reads."""
    cfg, e_dense = _engine(block_size=0)
    _submit(cfg, e_dense)
    e_dense.run()
    cfg2, e_paged = _engine(block_size=8)
    _submit(cfg2, e_paged)
    s = e_paged.run()
    for rid in e_dense.requests:
        assert (e_dense.requests[rid].outputs
                == e_paged.requests[rid].outputs), rid
    # the paged gather engaged and skipped pages
    assert s["blocks_touched_per_step"] > 0
    assert s["blocks_touched_per_step"] < s["blocks_window_per_step"]


def test_paged_fastpath_micro_loop_matches():
    cfg, e_sync = _engine(block_size=8, micro_steps=1)
    _submit(cfg, e_sync)
    e_sync.run()
    cfg2, e_fast = _engine(block_size=8, micro_steps=4)
    _submit(cfg2, e_fast)
    summary = e_fast.run()
    for rid in e_sync.requests:
        assert (e_sync.requests[rid].outputs
                == e_fast.requests[rid].outputs), rid
    assert summary["decode_dispatches"] < summary["decode_device_steps"]


def test_paged_single_dispatch_per_step_and_donation():
    """Block tables don't break the fused fast path: ONE decode dispatch
    per engine step, and the cache (incl. pools), PAM state (incl. the
    block table) and token vector are donated."""
    cfg, eng = _engine(block_size=8, max_batch=2)
    _submit(cfg, eng, n=2, plen=20, max_new=6)

    calls = {"decode": 0}
    fused_real = eng._get_micro(1)
    eng._micro_jits[1] = (
        lambda *a, **k: (calls.__setitem__("decode", calls["decode"] + 1),
                         fused_real(*a, **k))[1])
    eng.step()
    assert calls["decode"] == 1
    pk_buf = eng.cache.pk
    tbl_buf = eng.pam_state.block_table
    k_buf = eng.cache.k
    for _ in range(3):
        eng.step()
    assert calls["decode"] == 4
    assert eng.decode_dispatches == 4
    assert pk_buf.is_deleted()          # pools donated, not copied
    assert tbl_buf.is_deleted()         # table rides the donated state
    assert k_buf.is_deleted()


def test_paged_capacity_backpressure_and_reuse():
    """A pool too small for two concurrent windows serializes admission
    (OutOfBlocks never escapes), recycles freed blocks, and finishes
    every request."""
    # each request needs ceil((20+6)/8) = 4 blocks; pool holds 5
    cfg, eng = _engine(block_size=8, pool_blocks=5, max_batch=3)
    _submit(cfg, eng, n=3, plen=20, max_new=6)
    out = eng.run()
    assert out["finished"] == 3
    assert eng.allocator.check_no_double_mapping()
    assert eng.allocator.free_blocks == 5
    assert out["pool_occupancy_peak"] <= 1.0
    for rid, rs in eng.requests.items():
        assert len(rs.outputs) == rs.request.max_new_tokens, rid


def test_paged_config_validation():
    cfg, params = build_model("qwen3-0.6b")
    with pytest.raises(ValueError):   # paged requires PAM tiers
        EngineSpec(model=cfg, serving=ServingConfig(
            max_batch=2, max_len=64, block_size=8)).build(params)
    pam = PAMManagerConfig(max_tokens=60, hot_capacity=4, warm_capacity=8)
    with pytest.raises(ValueError):   # max_len must be a block multiple
        EngineSpec(model=cfg, serving=ServingConfig(
            max_batch=2, max_len=60, pam=pam, block_size=8)).build(params)
    pam64 = PAMManagerConfig(max_tokens=64, hot_capacity=4,
                             warm_capacity=8)
    with pytest.raises(ValueError):   # pool_blocks must be positive
        EngineSpec(model=cfg, serving=ServingConfig(
            max_batch=2, max_len=64, pam=pam64, block_size=8,
            pool_blocks=0)).build(params)


def test_unservable_request_fails_loudly():
    """A request whose window can never fit the pool raises instead of
    starving the queue forever (backpressure only helps when waiting
    can)."""
    cfg, eng = _engine(block_size=8, pool_blocks=2)
    _submit(cfg, eng, n=1, plen=20, max_new=6)   # needs 4 blocks > 2
    with pytest.raises(ValueError, match="blocks"):
        eng.run()


def test_paged_cache_requires_append_coords():
    """decode_step refuses a paged cache without append coordinates —
    a silent dense fall-back would desync the pool mirror."""
    cfg, params = build_model("qwen3-0.6b")
    cache = tf.init_decode_cache(cfg, 2, 32, paged_blocks=8, block_size=8)
    with pytest.raises(ValueError):
        tf.decode_step(cfg, params, jnp.zeros((2,), jnp.int32), cache)


def test_init_decode_cache_rejects_paged_for_cacheless_family():
    cfg = build_model("mamba2-780m")[0]
    with pytest.raises(ValueError):
        tf.init_decode_cache(cfg, 2, 32, paged_blocks=8, block_size=8)
