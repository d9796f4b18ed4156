"""Per-kernel correctness: Pallas (interpret=True) vs pure-jnp oracles,
swept over shapes and dtypes per the kernel-test contract."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ref
from repro.kernels.flash_attention import flash_attention
from repro.kernels.ssd_scan import ssd_scan
from repro.kernels import ops

jax.config.update("jax_platform_name", "cpu")


def _tol(dtype):
    return dict(rtol=2e-2, atol=2e-2) if dtype == jnp.bfloat16 else \
        dict(rtol=2e-5, atol=2e-5)


# ------------------------------------------------------------ flash_attention
@pytest.mark.parametrize("B,H,Hkv,S,d", [
    (1, 2, 2, 64, 32),       # MHA, one block
    (2, 4, 2, 96, 16),       # GQA, ragged seq vs block
    (1, 8, 1, 200, 64),      # MQA, multi-block with padding
    (2, 2, 2, 130, 8),       # tiny d, cross-block causal boundary
])
@pytest.mark.parametrize("causal", [True, False])
def test_flash_attention_matches_ref(B, H, Hkv, S, d, causal):
    key = jax.random.PRNGKey(B * 100 + H + S)
    q = jax.random.normal(jax.random.fold_in(key, 0), (B, H, S, d))
    k = jax.random.normal(jax.random.fold_in(key, 1), (B, Hkv, S, d))
    v = jax.random.normal(jax.random.fold_in(key, 2), (B, Hkv, S, d))
    out = flash_attention(q, k, v, causal=causal, block_q=64, block_k=64,
                          interpret=True)
    want = ref.flash_attention_ref(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               **_tol(jnp.float32))


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention_dtypes(dtype):
    key = jax.random.PRNGKey(7)
    B, H, Hkv, S, d = 1, 4, 2, 128, 32
    q = jax.random.normal(jax.random.fold_in(key, 0), (B, H, S, d), dtype)
    k = jax.random.normal(jax.random.fold_in(key, 1), (B, Hkv, S, d), dtype)
    v = jax.random.normal(jax.random.fold_in(key, 2), (B, Hkv, S, d), dtype)
    out = flash_attention(q, k, v, causal=True, block_q=64, block_k=64,
                          interpret=True)
    want = ref.flash_attention_ref(q, k, v, causal=True)
    assert out.dtype == dtype
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(want, np.float32), **_tol(dtype))


def test_flash_attention_long_context_stability():
    """Large logits must not overflow (online rescaling)."""
    key = jax.random.PRNGKey(3)
    B, H, S, d = 1, 1, 256, 16
    q = 30.0 * jax.random.normal(jax.random.fold_in(key, 0), (B, H, S, d))
    k = 30.0 * jax.random.normal(jax.random.fold_in(key, 1), (B, H, S, d))
    v = jax.random.normal(jax.random.fold_in(key, 2), (B, H, S, d))
    out = flash_attention(q, k, v, causal=True, block_q=64, block_k=64,
                          interpret=True)
    assert bool(jnp.all(jnp.isfinite(out)))
    want = ref.flash_attention_ref(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=1e-4, atol=1e-4)


# --------------------------------------------------------------- flash_decode
@pytest.mark.parametrize("B,H,Hkv,S,d,bs", [
    (1, 4, 4, 128, 32, 64),     # MHA two splits
    (2, 8, 2, 300, 16, 128),    # GQA, padding in last split
    (1, 16, 1, 64, 64, 64),     # MQA single split
    (3, 4, 2, 1024, 8, 256),    # many splits
])
def test_flash_decode_matches_ref(B, H, Hkv, S, d, bs):
    key = jax.random.PRNGKey(S + d)
    q = jax.random.normal(jax.random.fold_in(key, 0), (B, H, d))
    k = jax.random.normal(jax.random.fold_in(key, 1), (B, Hkv, S, d))
    v = jax.random.normal(jax.random.fold_in(key, 2), (B, Hkv, S, d))
    mask = jax.random.uniform(jax.random.fold_in(key, 3), (B, S)) < 0.7
    # guarantee at least one live token per row
    mask = mask.at[:, 0].set(True)
    out = ops.decode_attention(q, k, v, mask, block_s=bs, interpret=True)
    want = ref.flash_decode_ref(q, k, v, mask)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=1e-4, atol=1e-4)


def test_flash_decode_kv_len():
    """kv_len must exclude tokens past the live length even if mask=None."""
    key = jax.random.PRNGKey(9)
    B, H, Hkv, S, d = 2, 4, 4, 96, 16
    q = jax.random.normal(jax.random.fold_in(key, 0), (B, H, d))
    k = jax.random.normal(jax.random.fold_in(key, 1), (B, Hkv, S, d))
    v = jax.random.normal(jax.random.fold_in(key, 2), (B, Hkv, S, d))
    out = ops.decode_attention(q, k, v, None, kv_len=40, block_s=32,
                               interpret=True)
    want = ref.flash_decode_ref(q, k, v, None, kv_len=40)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=1e-4, atol=1e-4)


def test_flash_decode_ragged_kv_lens():
    """Per-sequence dynamic kv_lens (the serving engine's ragged batch)
    folds into the participation mask — equals per-batch masking."""
    key = jax.random.PRNGKey(13)
    B, H, Hkv, S, d = 2, 4, 2, 64, 16
    q = jax.random.normal(jax.random.fold_in(key, 0), (B, H, d))
    k = jax.random.normal(jax.random.fold_in(key, 1), (B, Hkv, S, d))
    v = jax.random.normal(jax.random.fold_in(key, 2), (B, Hkv, S, d))
    lens = jnp.array([50, 17], jnp.int32)
    out = ops.decode_attention(q, k, v, None, kv_lens=lens, block_s=32,
                               interpret=True)
    live = jnp.arange(S)[None, :] < lens[:, None]
    want = ref.flash_decode_ref(q, k, v, live)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=1e-4, atol=1e-4)


def test_flash_decode_dead_row_is_merge_identity():
    """The split walk carries (m, l, acc) across splits in-kernel; a row
    with no live token comes out as the merge identity (m=-inf, l=0,
    o=0), exactly as the jnp local-attention partial."""
    from repro.core import online_softmax as osm
    key = jax.random.PRNGKey(23)
    B, H, Hkv, S, d = 2, 4, 2, 96, 16
    q = jax.random.normal(jax.random.fold_in(key, 0), (B, H, d))
    k = jax.random.normal(jax.random.fold_in(key, 1), (B, Hkv, S, d))
    v = jax.random.normal(jax.random.fold_in(key, 2), (B, Hkv, S, d))
    mask = (jax.random.uniform(jax.random.fold_in(key, 3), (B, S)) < 0.5
            ).at[1].set(False)
    got = ops.decode_attention_partial(q, k, v, mask, block_s=32,
                                       interpret=True)
    want = osm.local_attention(
        q.reshape(B, Hkv, H // Hkv, d), k[:, :, None], v[:, :, None],
        mask=mask[:, None, None, :])
    want = osm.AttnPartial(want.o.reshape(B, H, d), want.m.reshape(B, H),
                           want.l.reshape(B, H))
    assert np.isneginf(np.asarray(got.m[1])).all()
    for a, b in zip(got, want):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-5, atol=1e-5)


def test_masked_decode_attention_kernel_equals_einsum():
    """ops.masked_decode_attention: Pallas-kernel path (interpret) and the
    grouped-einsum fallback agree on output AND per-token mass."""
    key = jax.random.PRNGKey(17)
    B, H, Hkv, S, d = 2, 4, 2, 48, 16
    q = jax.random.normal(jax.random.fold_in(key, 0), (B, H, d))
    k = jax.random.normal(jax.random.fold_in(key, 1), (B, Hkv, S, d))
    v = jax.random.normal(jax.random.fold_in(key, 2), (B, Hkv, S, d))
    part = jax.random.uniform(jax.random.fold_in(key, 3), (B, S)) < 0.7
    part = part.at[:, 0].set(True)
    lens = jnp.array([40, 23], jnp.int32)
    out_k, mass_k = ops.masked_decode_attention(q, k, v, part, lens,
                                                use_kernel=True)
    out_e, mass_e = ops.masked_decode_attention(q, k, v, part, lens,
                                                use_kernel=False)
    np.testing.assert_allclose(np.asarray(out_k), np.asarray(out_e),
                               rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(np.asarray(mass_k), np.asarray(mass_e),
                               rtol=1e-4, atol=1e-4)


def test_pam_decode_attention_tiers_equals_dense():
    """Alg. 1 across 3 uneven tier pools == dense attention over the
    concatenated KV — the paper's exactness claim, at kernel level."""
    key = jax.random.PRNGKey(21)
    B, H, Hkv, d = 2, 4, 2, 32
    sizes = (32, 96, 160)     # hot < warm < cold (uneven)
    ks, vs, masks = [], [], []
    for i, s_t in enumerate(sizes):
        ks.append(jax.random.normal(jax.random.fold_in(key, 3 * i), (B, Hkv, s_t, d)))
        vs.append(jax.random.normal(jax.random.fold_in(key, 3 * i + 1), (B, Hkv, s_t, d)))
        m = jax.random.uniform(jax.random.fold_in(key, 3 * i + 2), (B, s_t)) < 0.8
        masks.append(m.at[:, 0].set(True))
    q = jax.random.normal(jax.random.fold_in(key, 99), (B, H, d))

    out = ops.pam_decode_attention(q, list(zip(ks, vs)), masks,
                                   interpret=True)

    k_all = jnp.concatenate(ks, axis=2)
    v_all = jnp.concatenate(vs, axis=2)
    m_all = jnp.concatenate(masks, axis=1)
    want = ref.flash_decode_ref(q, k_all, v_all, m_all)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_decode_dtypes(dtype):
    key = jax.random.PRNGKey(17)
    B, H, Hkv, S, d = 1, 4, 2, 256, 32
    q = jax.random.normal(jax.random.fold_in(key, 0), (B, H, d), dtype)
    k = jax.random.normal(jax.random.fold_in(key, 1), (B, Hkv, S, d), dtype)
    v = jax.random.normal(jax.random.fold_in(key, 2), (B, Hkv, S, d), dtype)
    out = ops.decode_attention(q, k, v, None, block_s=128, interpret=True)
    want = ref.flash_decode_ref(q, k, v, None)
    assert out.dtype == dtype
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(want, np.float32), **_tol(dtype))


# ------------------------------------------------------------------ ssd_scan
@pytest.mark.parametrize("B,L,H,G,N,P,chunk", [
    (1, 64, 2, 1, 16, 8, 32),     # multi-chunk
    (2, 100, 4, 2, 8, 16, 64),    # padding + groups
    (1, 32, 2, 2, 32, 32, 32),    # single chunk
])
def test_ssd_scan_matches_sequential_ref(B, L, H, G, N, P, chunk):
    key = jax.random.PRNGKey(L + N)
    x = jax.random.normal(jax.random.fold_in(key, 0), (B, L, H, P))
    dt = jax.nn.softplus(
        jax.random.normal(jax.random.fold_in(key, 1), (B, L, H)))
    a = -jnp.exp(jax.random.normal(jax.random.fold_in(key, 2), (H,)) * 0.5)
    b = jax.random.normal(jax.random.fold_in(key, 3), (B, L, G, N)) / np.sqrt(N)
    c = jax.random.normal(jax.random.fold_in(key, 4), (B, L, G, N)) / np.sqrt(N)
    d_skip = jax.random.normal(jax.random.fold_in(key, 5), (H,))
    out = ssd_scan(x, dt, a, b, c, d_skip, chunk=chunk, interpret=True)
    want = ref.ssd_scan_ref(x, dt, a, b, c, d_skip)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=2e-4, atol=2e-4)


def test_ssd_scan_long_decay_stability():
    """Strong decay over many chunks stays finite and accurate."""
    key = jax.random.PRNGKey(5)
    B, L, H, G, N, P = 1, 256, 2, 1, 16, 8
    x = jax.random.normal(jax.random.fold_in(key, 0), (B, L, H, P))
    dt = jnp.full((B, L, H), 2.0)
    a = jnp.array([-4.0, -0.01])
    b = jax.random.normal(jax.random.fold_in(key, 1), (B, L, G, N)) / 4.0
    c = jax.random.normal(jax.random.fold_in(key, 2), (B, L, G, N)) / 4.0
    d_skip = jnp.zeros((H,))
    out = ssd_scan(x, dt, a, b, c, d_skip, chunk=64, interpret=True)
    want = ref.ssd_scan_ref(x, dt, a, b, c, d_skip)
    assert bool(jnp.all(jnp.isfinite(out)))
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=2e-4, atol=2e-4)
