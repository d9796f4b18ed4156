"""Profiler scopes, program names and host spans of the serving path.

The device programs name their work with ``jax.named_scope`` so that
each op's ``op_name`` says which part of the program it comes from; the
chip benchmark's readers match program and kernel names in the trace.
These tests pin both: every scope is in the lowered programs, every
program compiles under a name of its own, no scope or program name
holds another program's reader needle, and turning a profiler on
changes no token.
"""

import functools
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from conftest import build_model, make_engine, make_pam, make_requests

from repro.kernels import flash_decode as fd
from repro.kernels import ops
from repro.obs import metrics as obs_metrics
from repro.serving import engine as E

# substrings the chip benchmark's readers match in op and program
# labels (bench/trace.time_of)
NEEDLES = ("jit_run_k", "jit_pre", "flash_decode_paged")

DECODE_SCOPES = ("pam.participation", "pam.observe", "pam.schedule",
                 "pam.mass", "pam.stats", "kv.append", "kv.relayout",
                 "attn.hot", "attn.paged", "attn.merge", "model.qkv",
                 "model.mlp", "model.head")
PREFILL_SCOPES = ("model.prefill_attention", "model.prefill_mlp")
COMMIT_SCOPES = ("kv.prefill_write", "kv.ring_relayout", "pam.place",
                 "model.sample")

SCOPE_RE = re.compile(r"(?:^|/)((?:pam|kv|attn|model)\.\w+)")


def scopes_of(text: str) -> set[str]:
    """Scope names in the locations of a lowered program's text."""
    out = set()
    for loc in re.findall(r'loc\("([^"]*)"', text):
        out.update(SCOPE_RE.findall(loc))
    return out


def module_name(lowered) -> str:
    return re.search(r"module @(\w+)", lowered.as_text()).group(1)


@pytest.fixture(scope="module")
def model():
    return build_model("qwen3-0.6b")


def _engine(model, **kw):
    cfg, params = model
    args = dict(pam=make_pam(max_len=48), max_batch=2, max_len=48,
                block_size=8, hot_window=16)
    args.update(kw)
    return make_engine(cfg, params, **args)


def _sds(x):
    return jax.ShapeDtypeStruct(x.shape, x.dtype)


def _prefill_and_commit(eng, n=2, bucket=32):
    """Lowered prefill and admission commit of ``eng`` for a group of
    ``n`` prompts in the ``bucket`` bucket."""
    pre = eng._prefill_for_len(bucket)
    toks = jax.ShapeDtypeStruct((n, bucket), jnp.int32)
    lens = jax.ShapeDtypeStruct((n,), jnp.int32)
    logits, sub = jax.eval_shape(pre, eng.params, toks, lens)
    nb = eng.scfg.max_len // eng.block_size
    commit = E._admit_commit_fn(eng.pam_cfg, eng.block_size, n,
                                hot_window=eng.hot_window)
    i32 = functools.partial(jax.ShapeDtypeStruct, dtype=jnp.int32)
    low_commit = commit.lower(
        jax.tree.map(_sds, eng.cache), jax.tree.map(_sds, eng.pam_state),
        _sds(eng.tokens_dev), sub, logits, i32((n,)), i32((n,)),
        jax.ShapeDtypeStruct((n,), jnp.uint32), i32((n, nb)))
    return pre.lower(eng.params, toks, lens), low_commit


def test_decode_step_carries_every_scope(model, monkeypatch):
    """The fused decode step as the chip runs it: the paged kernel path
    (in interpret mode here), so the relayout around the kernel call is
    in the program too."""
    monkeypatch.setattr(ops, "_on_tpu", lambda: True)
    monkeypatch.setattr(ops, "_flash_decode_paged", lambda *a, **k: (
        fd.flash_decode_paged(*a, **{**k, "interpret": True})))
    # trace afresh with the kernel path patched in, and leave no traced
    # program of it behind for later tests
    jax.clear_caches()
    try:
        text = _engine(model, max_batch=3).lower_decode_step(1).as_text(
            debug_info=True)
    finally:
        jax.clear_caches()
    missing = set(DECODE_SCOPES) - scopes_of(text)
    assert not missing, missing
    assert "flash_decode_paged" in text


def test_prefill_and_admission_commit_carry_their_scopes(model):
    eng = _engine(model)
    pre, commit = _prefill_and_commit(eng)
    assert set(PREFILL_SCOPES) <= scopes_of(pre.as_text(debug_info=True))
    got = scopes_of(commit.as_text(debug_info=True))
    assert set(COMMIT_SCOPES) <= got, set(COMMIT_SCOPES) - got


def _all_programs(eng):
    """Every program the engine dispatches, lowered for its shapes."""
    n, bucket = 2, 16
    L = eng.cfg.n_layers
    hkv, dh = eng.cfg.n_kv_heads, eng.cfg.head_dim
    smax = eng.scfg.max_len
    nb = smax // eng.block_size
    i32 = functools.partial(jax.ShapeDtypeStruct, dtype=jnp.int32)
    kv_dt = eng.cache.pk.dtype
    cache, pam = (jax.tree.map(_sds, eng.cache),
                  jax.tree.map(_sds, eng.pam_state))
    toks = _sds(eng.tokens_dev)
    pre, commit = _prefill_and_commit(eng, n, bucket)
    spre = E._suffix_prefill_fn(eng.cfg, smax)
    args = (eng.params, i32((n, bucket)), cache.pk, cache.pv, i32((n, nb)),
            i32((n,)), i32((n,)))
    logits, suf_k, suf_v = jax.eval_shape(spre, *args)
    scommit = E._suffix_commit_fn(eng.pam_cfg, eng.block_size, n,
                                  hot_window=eng.hot_window)
    fill = E._chunk_fill_fn(eng.cfg, smax)
    imp = E._import_commit_fn(True, eng.block_size, eng.hot_window)
    row = jax.ShapeDtypeStruct((L, hkv, smax, dh), kv_dt)
    f32 = jax.ShapeDtypeStruct((smax,), jnp.float32)
    exp = E._export_gather_fn(eng.block_size, eng.hot_window)
    return [
        eng.lower_decode_step(1), pre, commit, spre.lower(*args),
        scommit.lower(cache, pam, toks, suf_k, suf_v, logits, i32((n,)),
                      i32((n,)), jax.ShapeDtypeStruct((n,), jnp.uint32),
                      i32((n, nb)), i32((n, bucket)), i32((n, bucket)),
                      i32((n,)), i32((n,))),
        fill.lower(eng.params, cache, i32((1, bucket)), i32((nb,)), i32(()),
                   i32(()), i32((bucket,)), i32((bucket,)), i32(()),
                   i32(())),
        imp.lower(cache, pam, toks, row, row, f32, i32((smax,)),
                  jax.ShapeDtypeStruct((smax,), jnp.bool_), i32(()),
                  i32(()), i32(()), i32((nb,))),
        exp.lower(cache.k, cache.v, cache.pk, cache.pv, i32((nb,)),
                  i32((smax,)), i32(()), i32(())),
    ]


def test_every_program_compiles_under_its_own_name(model):
    eng = _engine(model)
    names = [module_name(low) for low in _all_programs(eng)]
    assert names == ["jit_run_k", "jit_pre", "jit_admit_commit",
                     "jit_suffix_pre", "jit_suffix_commit",
                     "jit_chunk_fill", "jit_import_commit",
                     "jit_export_gather"]


def test_no_new_name_contains_a_reader_needle(model):
    eng = _engine(model)
    lowered = _all_programs(eng)
    found = set()
    for low in lowered:
        found |= scopes_of(low.as_text(debug_info=True))
        name = module_name(low)
        if name not in ("jit_run_k", "jit_pre"):
            assert not any(nd in name for nd in NEEDLES), name
    assert {"pam.mass", "kv.append", "model.sample"} <= found
    for scope in found:
        assert not any(nd in scope for nd in NEEDLES), scope


def _streams(model, tmp_path=None):
    eng = _engine(model, max_batch=2)
    for r in make_requests(4, model[0].vocab, plen=20, max_new=10):
        eng.submit(r)
    if tmp_path is None:
        eng.run()
    else:
        with jax.profiler.trace(str(tmp_path)):
            eng.run()
    return {rid: list(rs.outputs) for rid, rs in eng.requests.items()}


def test_token_streams_identical_with_a_profiler_open(model, tmp_path):
    off = _streams(model)
    on = _streams(model, tmp_path)
    assert on == off
    assert all(len(v) == 10 for v in on.values())


def test_engine_spans_reach_the_profiler(model, tmp_path):
    """The engine's spans land on the profiler's host timeline (CPU
    here), under names the benchmark's own client spans do not use."""
    from jax.profiler import ProfileData
    _streams(model, tmp_path)
    path = next(tmp_path.rglob("*.xplane.pb"))
    names = {ev.name for plane in ProfileData.from_file(str(path)).planes
             for line in plane.lines for ev in line.events}
    spans = {"engine.step", "engine.admit", "engine.prefill_dispatch",
             "engine.commit_dispatch", "engine.first_token_readback",
             "engine.decode_dispatch", "engine.readback", "engine.emit"}
    assert spans <= names, spans - names
    client = {"pump", "engine_step", "admission", "fanout", "submit",
              "bench.window"}
    assert not spans & client


def test_queue_wait_counts_one_observation_per_admission(model):
    cfg, params = model
    with obs_metrics.use(obs_metrics.MetricsRegistry()) as reg:
        eng = _engine(model, max_batch=2)
        reqs = make_requests(5, cfg.vocab, plen=12, max_new=6)
        for r in reqs:
            eng.submit(r)
        eng.run()
        h = reg.snapshot()["histograms"][
            'pam_engine_queue_wait_seconds{device="dev"}']
    assert h["count"] == len(reqs)
    # two slots: the last three requests waited for a free one
    assert h["max"] > 0.0
    assert np.isfinite(h["sum"])
