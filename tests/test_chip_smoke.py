"""``chip_smoke.py``'s body on CPU at reduced widths, so the chip smoke
path cannot rot between chip runs — in float32 and in bfloat16 (the
served dtype, which also puts the bf16 ring and pool path under a CPU
test). Off the chip the fused decode step takes the jnp reference, and
the paged kernel check runs the kernel in interpret mode."""

import dataclasses
import importlib.util
import os
import subprocess
import sys

import jax
import pytest

from repro.models.config import get_config, reduced

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SCRIPT = os.path.join(REPO, "chip_smoke.py")

_spec = importlib.util.spec_from_file_location("chip_smoke", SCRIPT)
chip_smoke = importlib.util.module_from_spec(_spec)
sys.modules["chip_smoke"] = chip_smoke
_spec.loader.exec_module(chip_smoke)

SHAPE = chip_smoke.SmokeShape(requests=4, prompt_len=24, new_tokens=6,
                              max_len=64, block_size=8, hot_window=16)


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_smoke_body_serves_and_checks(dtype):
    cfg = dataclasses.replace(reduced(get_config(chip_smoke.ARCH)),
                              dtype=dtype)
    out = chip_smoke.run_smoke(cfg, SHAPE, seed=1, expect_kernel=False)
    assert [p["tokens"] for p in out["serve"]] == [
        SHAPE.requests * SHAPE.new_tokens] * 2
    assert out["kernel_in_step"] is False       # jnp reference off-chip
    assert out["kernel_max_abs_err"] <= chip_smoke.KERNEL_ATOL
    assert out["build"]["dtype"] == dtype


def test_smoke_refuses_without_a_tpu():
    """On the CPU the script exits non-zero and prints no result line."""
    res = subprocess.run([sys.executable, SCRIPT], capture_output=True,
                         text=True, timeout=300,
                         env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert res.returncode == 2
    assert '"ok"' not in res.stdout


def test_compile_cache_dir(monkeypatch):
    """The environment's cache directory wins; otherwise one fixed,
    gitignored directory inside the checkout."""
    from repro.launch import compile_cache as cc
    before = jax.config.jax_compilation_cache_dir
    try:
        monkeypatch.setenv(cc.ENV_VAR, "/elsewhere")
        assert cc.enable_compile_cache() == "/elsewhere"
        assert jax.config.jax_compilation_cache_dir == before
        monkeypatch.delenv(cc.ENV_VAR)
        assert cc.enable_compile_cache() == os.path.join(REPO, ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == cc.DEFAULT_DIR
        with open(os.path.join(REPO, ".gitignore")) as f:
            assert ".jax_cache/" in f.read().split()
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
