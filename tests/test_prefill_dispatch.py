"""Which attention a from-scratch prefill runs, and what it computes.

``ServingEngine`` takes the Pallas ``flash_prefill`` kernel for its
prefill's attention only where it can observe that the kernel applies:
a TPU backend, an unsharded engine, heads a whole number of lanes wide,
a GQA model (dense, or MoE without MLA). Everywhere else, the
CPU among them, the jnp ``chunked_attention`` path runs as it did. Forced
here in interpret mode, the kernel must reproduce the jnp path's logits
and the K/V of every live position, and the engine counts each prefill
dispatch that took it.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from conftest import make_engine, make_requests

from repro.kernels import ops
from repro.models import transformer as tf
from repro.models.config import get_config, reduced
from repro.obs import metrics as obs_metrics
from repro.serving import engine as E

BUCKET = 256
# bf16 rounding alone: the jnp path moves by up to 1.5e-2 (relative, per
# position) when only its probabilities are rounded to bf16 before PV
# (2**-8 is bf16's epsilon); a wrong mask or skip moves a position by O(1)
REL_TOL = 3e-2


def _rel_err(got, want, axis):
    got, want = (np.asarray(x, np.float32) for x in (got, want))
    return (np.linalg.norm(got - want, axis=axis)
            / np.linalg.norm(want, axis=axis))


def _runs_kernel(fn, *args) -> bool:
    return "flash_prefill" in str(jax.make_jaxpr(fn)(*args))


def _model(dtype, d_head=128):
    """Reduced qwen3-0.6b with lane-wide heads, so the kernel applies."""
    cfg = dataclasses.replace(reduced(get_config("qwen3-0.6b")),
                              d_head=d_head, dtype=dtype)
    return cfg, tf.init_params(cfg, jax.random.PRNGKey(0))


@pytest.fixture(scope="module")
def bf16_model():
    return _model("bfloat16")


def _prompts(cfg, lens, bucket=BUCKET):
    rng = np.random.default_rng(3)
    toks = np.zeros((len(lens), bucket), np.int32)
    for i, n in enumerate(lens):
        toks[i, :n] = rng.integers(0, cfg.vocab, n)
    return jnp.asarray(toks), jnp.asarray(lens, jnp.int32)


def test_kernel_choice_follows_backend_sharding_and_head_dim(monkeypatch):
    dense = _model("float32")[0]
    narrow = reduced(get_config("qwen3-0.6b"))              # 16-wide heads
    mla = dataclasses.replace(reduced(get_config("deepseek-v2-lite-16b")),
                              d_head=128)
    hybrid = dataclasses.replace(reduced(get_config("zamba2-7b")),
                                 d_head=128)
    assert not E._prefill_kernel(dense)                     # the CPU
    monkeypatch.setattr(ops, "_on_tpu", lambda: True)
    assert E._prefill_kernel(dense)
    assert ops.prefill_kernel_applies(256)
    assert not E._prefill_kernel(dense, rep=object())       # sharded
    assert not E._prefill_kernel(narrow)
    assert not E._prefill_kernel(mla)                       # no GQA prefill
    assert not E._prefill_kernel(hybrid)                    # jnp shared attn


def test_cpu_prefill_is_the_jnp_path(bf16_model):
    """On the CPU the engine's prefill program is the jnp path's, bit for
    bit: the same logits and the same K/V."""
    cfg, params = bf16_model
    eng = make_engine(cfg, params, max_batch=2, max_len=BUCKET)
    assert not eng.prefill_kernel
    toks, lens = _prompts(cfg, (100, BUCKET))
    got_logits, got = eng._prefill_for_len(BUCKET)(params, toks, lens)
    want_logits, want = jax.jit(
        lambda p, t, n: tf.prefill(cfg, p, t, BUCKET, true_len=n))(
            params, toks, lens)
    np.testing.assert_array_equal(np.asarray(got_logits),
                                  np.asarray(want_logits))
    np.testing.assert_array_equal(np.asarray(got.k), np.asarray(want.k))
    np.testing.assert_array_equal(np.asarray(got.v), np.asarray(want.v))


def test_kernel_prefill_matches_jnp_below_true_len(bf16_model):
    """The kernel (interpret mode) against the jnp path in a bf16 model:
    the last live token's logits and the K/V of every live position; the
    dead positions past ``true_len`` are not compared."""
    cfg, params = bf16_model
    toks, lens = _prompts(cfg, (77, 200))

    def run(kernel):
        pre = jax.jit(lambda p, t, n: tf.prefill(
            cfg, p, t, BUCKET, true_len=n, attn_kernel=kernel))
        assert _runs_kernel(pre, params, toks, lens) == kernel
        return pre(params, toks, lens)

    (lk, ck), (lj, cj) = run(True), run(False)
    np.testing.assert_array_equal(np.asarray(ck.lengths), np.asarray(lens))
    assert _rel_err(lk, lj, axis=1).max() < REL_TOL
    for b, n in enumerate(np.asarray(lens)):
        for got, want in ((ck.k, cj.k), (ck.v, cj.v)):
            # per layer and position, over its heads
            assert _rel_err(got[:, b, :, :n], want[:, b, :, :n],
                            axis=(1, 3)).max() < REL_TOL


def test_engine_counts_kernel_prefills(monkeypatch):
    """Forced on the CPU (interpret mode), every group prefill takes the
    kernel and counts once; the streams equal the jnp engine's."""
    cfg, params = _model("float32")
    reqs = make_requests(3, cfg.vocab, plen=20, max_new=6)
    reqs[2] = dataclasses.replace(reqs[2], prompt=reqs[2].prompt[:5])

    def serve():
        with obs_metrics.use() as reg:
            eng = make_engine(cfg, params, max_batch=4, max_len=64)
            for r in reqs:
                eng.submit(r)
            eng.run()
        streams = {i: rs.outputs for i, rs in eng.requests.items()}
        return eng, reg.get('pam_engine_prefill_kernel_total{device="dev"}'
                            ), streams

    eng, count, want = serve()
    assert not eng.prefill_kernel and count == 0
    monkeypatch.setattr(E, "_prefill_kernel", lambda cfg, rep=None: True)
    eng, count, got = serve()
    assert eng.prefill_kernel
    assert count == eng.prefill_dispatches == 2     # buckets 32 and 8
    toks, lens = _prompts(cfg, (20,), bucket=32)
    assert _runs_kernel(eng._prefill_for_len(32), params, toks, lens)
    assert got == want


def test_kernel_prefill_of_an_unbucketed_prompt(monkeypatch):
    """Without buckets a prompt prefills at its own length: 200 tokens is
    past one lane tile and off a power of two, and the forced kernel's
    streams still equal the jnp engine's."""
    cfg, params = _model("float32")
    reqs = make_requests(2, cfg.vocab, plen=200, max_new=4)
    reqs[1] = dataclasses.replace(reqs[1], prompt=reqs[1].prompt[:131])

    def serve():
        eng = make_engine(cfg, params, max_batch=2, max_len=256,
                          bucket_prefill=False)
        for r in reqs:
            eng.submit(r)
        eng.run()
        return eng, {i: rs.outputs for i, rs in eng.requests.items()}

    _, want = serve()
    monkeypatch.setattr(E, "_prefill_kernel", lambda cfg, rep=None: True)
    eng, got = serve()
    toks, lens = _prompts(cfg, (200,), bucket=200)
    assert _runs_kernel(eng._prefill_for_len(200), params, toks, lens)
    assert got == want
