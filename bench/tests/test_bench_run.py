"""The command refuses to run without a TPU, and without the program."""

import os
import shutil
import subprocess
import sys

import _tiny


def _run(cwd, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    env.update(env_extra or {})
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload",
         "qwen3-0.6b.long-decode", "--seed", "3000000001", "--seconds", "1",
         "--trace", "0"], cwd=cwd, env=env, capture_output=True, text=True,
        timeout=300)


def test_refuses_without_a_tpu():
    p = _run(_tiny.ROOT)
    assert p.returncode == 2, p.stderr
    assert p.stdout.strip() == ""
    assert "needs a TPU" in p.stderr


def test_fails_without_the_program(tmp_path):
    shutil.copy(os.path.join(_tiny.ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(os.path.join(_tiny.ROOT, "bench"), tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = _run(str(tmp_path))
    assert p.returncode != 0
    assert p.stdout.strip() == ""


def test_every_cell_has_its_files():
    import json
    from bench import spec
    with open(os.path.join(_tiny.ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    for w in bench["workloads"]:
        c = spec.load_cell(w["name"])
        assert c.limits["max_logit_gap"] > 0
        assert c.traffic["serving"]["max_len"] >= c.traffic["prompt"]["hi"]
        spec.model_config(c.hf, c.config_name)
        spec.serving_config(c.traffic)
    for m in bench["per_layer"]:
        assert os.path.exists(os.path.join(_tiny.ROOT, "bench", "metrics",
                                           m["name"] + ".py"))
