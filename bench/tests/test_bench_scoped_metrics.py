"""The admission reader, and the decode-step readers on op labels that
carry the program's scope paths (``jit(run_k)/model.layers/.../pam.mass/
...``), as they would once the trace reduction loads each op's
``op_name``."""

import _tiny  # noqa: F401
import pytest

from bench import flops
from bench import trace as T
from bench.run import RunData, load_metric

MS = 1_000_000
HF = {"model_type": "qwen3", "num_hidden_layers": 2, "hidden_size": 128,
      "intermediate_size": 256,
      "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 32,
      "vocab_size": 512, "torch_dtype": "bfloat16"}
PEAK = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
RUN_K = "jit(run_k)/model.layers/while/body/closed_call/"


def decode_ops(t, scoped_paths):
    """One fused decode step's ops starting at ``t`` ms: the layer loop
    holding a scoped Alg. 2 ``while`` with a fusion nested in it, the
    pool relayout, the paged kernel and the importance mass."""
    def lab(name, path):
        return f"{name} {path}" if scoped_paths else name
    return [
        (lab("while.94", "jit(run_k)/model.layers/while"),
         t * MS, (t + 8) * MS),
        (lab("while.7", RUN_K + "pam.observe/pam.schedule/while"),
         (t + 1) * MS, (t + 3) * MS),
        (lab("fusion.8", RUN_K + "pam.observe/pam.schedule/while/body/sort"),
         (t + 1) * MS + MS // 2, (t + 2) * MS + MS // 2),
        (lab("reshape.3", RUN_K + "attn.paged/kv.relayout/reshape"),
         (t + 4) * MS, (t + 4) * MS + MS // 2),
        (lab("flash_decode_paged.13", RUN_K + "attn.paged/pallas_call"),
         (t + 5) * MS, (t + 7) * MS),
        (lab("fusion.12", RUN_K + "pam.mass/dot_general"),
         (t + 7) * MS, (t + 8) * MS),
    ]


def run_of(scoped_paths=True, steps=2, admissions=((20, 30, 6),)):
    """Decode steps every 10 ms, then admissions given as (start, prefill
    ms, commit ms). ``scoped_paths=False``: labels without scope paths,
    and the commit under a name that is not ``jit_admit_commit``."""
    commit = "jit_admit_commit(4)" if scoped_paths else "jit_commit(4)"
    ops, mods = [], []
    for i in range(steps):
        ops += decode_ops(10 * i, scoped_paths)
        mods.append(("jit_run_k(7)", 10 * i * MS, (10 * i + 8) * MS))
    end = 10 * steps
    for t, pre, com in admissions:
        mods += [("jit_pre(3)", t * MS, (t + pre) * MS),
                 (commit, (t + pre) * MS, (t + pre + com) * MS)]
        ops.append(("while.12 jit(pre)/model.layers/while",
                    t * MS, (t + pre) * MS))
        end = max(end, t + pre + com + 4)
    tr = T.from_events(ops, mods, [("bench.window", 0, end * MS)])
    st = [{"active": 2, "reads": 300, "paged_reads": 200, "dt": 0.01,
           "prefill_tokens": 0}] * steps
    return RunData(cell=None, hf=HF, trace=tr, pumps=[], steps=st,
                   admitted=[], peak=PEAK, flops=flops)


def test_admission_is_prefill_and_commit_per_commit():
    read = load_metric("admission_ms")
    # prefill [20, 50] and commit [50, 56] ms: one admission of 36 ms
    assert read(run_of()) == pytest.approx(36.0)
    # two admissions of 36 and 12 ms
    two = run_of(admissions=((20, 30, 6), (60, 10, 2)))
    assert read(two) == pytest.approx((36 + 12) / 2)


def test_admission_programs_that_overlap_count_once():
    run = run_of()
    plane = run.trace.planes[0]
    mods = plane.modules + [("jit_pre(3)", 25 * MS, 40 * MS)]
    run.trace = T.from_events(plane.ops, mods, run.trace.host)
    assert load_metric("admission_ms")(run) == pytest.approx(36.0)


def test_admission_is_none_without_a_commit():
    read = load_metric("admission_ms")
    # a commit under another name is not an admission commit
    assert read(run_of(scoped_paths=False)) is None
    assert read(run_of(admissions=())) is None


@pytest.mark.parametrize("name", ["decode_step_ms", "decode_mfu",
                                  "flash_decode_paged_roofline"])
def test_decode_readers_ignore_scope_paths(name):
    """No scope name contains a needle of these readers, so labels that
    carry scope paths leave what they read unchanged."""
    read = load_metric(name)
    with_paths, bare = read(run_of()), read(run_of(scoped_paths=False))
    assert with_paths is not None
    assert with_paths == pytest.approx(bare)
