"""The check that decides ``correct``, driven through a whole run of a
tiny cell on the CPU (the look for a chip skipped): a sound run passes,
the int8 and fp8 controls fail, and a token altered where it is produced
or a step that keeps its state fails."""

import numpy as np
import pytest

import _tiny
from bench import run as R
from bench import weights

SEED = 5


def _serve(cell, seed=SEED):
    params = weights.make_params(cell.hf, seed)
    served, _, setup_s = R.serve_cell(cell, params, seed, 2.0)
    return params, served, setup_s


@pytest.fixture(scope="module")
def sound():
    cell = _tiny.cell()
    params, served, setup_s = _serve(cell)
    checks, extra = R.check(cell, lambda: params, served, SEED,
                            controls=("int8", "fp8"))
    return cell, served, setup_s, checks, extra


def test_sound_run_is_correct_and_reports_its_metrics(sound):
    cell, served, setup_s, checks, _ = sound
    assert R.is_correct(checks), checks
    assert checks["checked_requests"]["value"] == 4
    assert served.finished == set(served.in_scope)
    m = R.end_to_end(cell, served, setup_s)
    assert {"out_tok_s", "itl_p95_ms", "setup_s"} <= set(m)
    assert m["out_tok_s"]["value"] > 0


def _fails(nums, checks):
    return any(nums[n] > checks[n]["limit"]
               for n in ("max_logit_gap", "mean_logit_gap"))


def test_int8_control_fails(sound):
    _, _, _, checks, extra = sound
    assert _fails(extra["controls"]["int8"], checks), extra["controls"]


def test_fp8_control_fails(sound):
    _, _, _, checks, extra = sound
    nums = extra["controls"]["fp8"]
    assert all(nums[n] > checks[n]["limit"]
               for n in ("max_logit_gap", "mean_logit_gap")), nums


def test_compared_positions_stop_where_participation_opens(sound):
    """Each request is compared up to the first position whose
    participation is open to rounding, and never past what it streamed."""
    cell, served, _, checks, extra = sound
    rids = R.sample_for_check(served, 4, SEED, 24)
    assert len(extra["compared"]) == len(rids)
    for r, n in zip(rids, extra["compared"]):
        assert 2 <= n <= min(len(served.tokens[r]), 24)
    assert extra["checked_tokens"] == sum(extra["compared"])


def _broken_run(monkeypatch, module, name, wrap):
    """A whole run with ``module.name`` replaced by ``wrap(real)`` under
    the timed path; returns its checks."""
    from repro.serving import engine as eng
    monkeypatch.setattr(module, name, wrap(getattr(module, name)))
    for fn in (eng._fused_decode_fn, eng._admit_commit_fn, eng._prefill_fn):
        fn.cache_clear()
    try:
        cell = _tiny.cell()
        params, served, _ = _serve(cell, seed=SEED + 1)
        checks, _ = R.check(cell, lambda: params, served, SEED + 1)
    finally:
        monkeypatch.undo()
        for fn in (eng._fused_decode_fn, eng._admit_commit_fn,
                   eng._prefill_fn):
            fn.cache_clear()
    return checks


def test_altered_token_fails(monkeypatch):
    """A token altered where it is produced (on-device sampling)."""
    from repro.serving import engine as eng

    def wrap(real):
        def altered(logits, *a, **k):
            return (real(logits, *a, **k) + 1) % logits.shape[-1]
        return altered

    checks = _broken_run(monkeypatch, eng, "_sample_tokens", wrap)
    assert not R.is_correct(checks)
    assert checks["max_logit_gap"]["value"] > 0.1


def test_step_that_keeps_its_state_fails(monkeypatch):
    """A decode step that returns its KV state unchanged: the appended
    token's keys and values are dropped, only the lengths advance."""
    from repro.models import transformer as tf

    def wrap(real):
        def stale(cfg, params, tokens, cache, **k):
            logits, new, scores = real(cfg, params, tokens, cache, **k)
            return logits, cache._replace(lengths=new.lengths), scores
        return stale

    checks = _broken_run(monkeypatch, tf, "decode_step", wrap)
    assert not R.is_correct(checks)


def test_sample_holds_the_longest_request(sound):
    _, served, _, _, _ = sound
    rids = R.sample_for_check(served, 4, SEED, 24)
    size = {r: len(served.in_scope[r].prompt) + len(served.tokens[r])
            for r in served.tokens}
    assert size[rids[0]] == max(size.values())
    assert len(set(rids)) == 4
    assert rids == R.sample_for_check(served, 4, SEED, 24)
    assert np.all([r in served.tokens for r in rids])
