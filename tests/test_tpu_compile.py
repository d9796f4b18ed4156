"""Ahead-of-time compiles of the serving decode kernels for a described
TPU v5e chip, at qwen3-0.6b's published widths in bf16.

Nothing runs: the TPU compiler installed with jaxlib lowers and compiles
each kernel for a chip that is described, not attached, and raises what
Mosaic would raise on the chip (block shapes off the tiling, VMEM
overuse). The topology is described inside a fixture, so that importing
this file never loads the TPU library.
"""

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import ops
from repro.kernels.flash_decode import flash_decode, flash_decode_paged
from repro.models.config import get_config

CFG = get_config("qwen3-0.6b")
H, HKV, D = CFG.n_heads, CFG.n_kv_heads, CFG.head_dim
B, SMAX = 8, 2048
BF16 = jnp.bfloat16


@pytest.fixture(scope="module")
def one_chip():
    import os
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means "cannot describe"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip cannot be read back from the
    # persistent cache without that chip: keep the cache out of it
    from jax.experimental.compilation_cache import compilation_cache
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", prev)


def _spec(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compiled_text(fn, *args):
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text, "kernel not in the compiled program"
    return text


def test_flash_decode_compiles(one_chip):
    q = _spec((B, H, D), BF16, one_chip)
    kv = _spec((B, HKV, SMAX, D), BF16, one_chip)
    mask = _spec((B, SMAX), jnp.bool_, one_chip)
    lens = _spec((B,), jnp.int32, one_chip)
    _compiled_text(
        lambda q, k, v, m, n: flash_decode(q, k, v, m, kv_lens=n),
        q, kv, kv, mask, lens)


@pytest.mark.parametrize("block_size", [16, 128])
def test_flash_decode_paged_compiles(one_chip, block_size):
    nb = SMAX // block_size
    pool = _spec((B * nb + 1, block_size, HKV, D), BF16, one_chip)
    q = _spec((B, H, D), BF16, one_chip)
    table = _spec((B, nb), jnp.int32, one_chip)
    mask = _spec((B, SMAX), jnp.bool_, one_chip)
    _compiled_text(flash_decode_paged, q, pool, pool, table, mask)


def test_paged_masked_decode_attention_compiles(one_chip, monkeypatch):
    """The paged ring engine's per-layer attention: hot ring partial,
    paged kernel partial, and the union-mass reconstruction. The
    wrapper asks JAX's default backend whether it runs on a TPU; here
    that is the CPU, so the test answers for it."""
    monkeypatch.setattr(ops, "_on_tpu", lambda: True)
    block_size, window = 16, 256
    nb = SMAX // block_size
    q = _spec((B, H, D), BF16, one_chip)
    ring = _spec((B, HKV, window, D), BF16, one_chip)
    pool = _spec((B * nb + 1, block_size, HKV, D), BF16, one_chip)
    table = _spec((B, nb), jnp.int32, one_chip)
    mask = _spec((B, SMAX), jnp.bool_, one_chip)
    lens = _spec((B,), jnp.int32, one_chip)

    def attn(q, kc, vc, pk, pv, bt, hot, pgd, lens):
        return ops.paged_masked_decode_attention(
            q, kc, vc, pk, pv, bt, hot, pgd, lens, use_kernel=True)

    _compiled_text(attn, q, ring, ring, pool, pool, table, mask, mask, lens)
