"""A toy four-chip cell through the harness on eight fake CPU devices
(``sharded_checks.py``, in a subprocess: the device count must be set
before JAX is imported)."""

import json
import os
import subprocess
import sys

import _tiny
import pytest


@pytest.fixture(scope="module")
def out():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=os.path.join(_tiny.ROOT, "src"))
    p = subprocess.run(
        [sys.executable, os.path.join(os.path.dirname(__file__),
                                      "sharded_checks.py")],
        env=env, capture_output=True, text=True, timeout=900)
    assert p.returncode == 0, p.stderr[-4000:]
    return json.loads(p.stdout.strip().splitlines()[-1])


def test_weights_are_made_sharded_on_the_cell_devices(out):
    assert out["devices"] == 8
    assert out["weights_devices"] == [0, 1, 2, 3]
    # a quarter of the tree, and the replicated norms
    assert out["weights_largest_share"] < 0.3


def test_the_engine_takes_the_weights_in_place(out):
    assert out["engine_shard"] == 4
    assert out["engine_params_in_place"]


def test_a_sound_sharded_run_is_correct(out):
    assert out["sound_correct"], out["sound_checks"]
    assert out["sound_checks"]["checked_requests"] == 4
    assert out["sound_finished"] == 8
    assert {"out_tok_s", "itl_p95_ms", "setup_s"} <= set(
        out["sound_metrics"])


def test_an_altered_token_fails_the_sharded_run(out):
    assert not out["altered_correct"]
    assert out["altered_max_logit_gap"] > 0.1
