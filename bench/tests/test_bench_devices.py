"""Traces read one device at a time: a one-device trace gives every
reader the number the merged reduction gave, and a four-device trace
gives the mean over the devices (not their union), the busiest device's
breakdown, and the collectives of the decode programs."""

import glob
import os

import _tiny
import pytest

from bench import flops
from bench import trace as T
from bench.run import RunData, load_metric

MS = 1_000_000
HF = {"model_type": "qwen3", "num_hidden_layers": 2, "hidden_size": 128,
      "intermediate_size": 256, "num_attention_heads": 4,
      "num_key_value_heads": 2, "head_dim": 32, "vocab_size": 512,
      "torch_dtype": "bfloat16"}
PEAK = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}

# what the readers gave on ``one_plane()`` before traces were read per
# device (the reduction that merged every device's ops into one list)
MERGED = {"admission_ms": 27.0, "decode_mfu": 0.00014067005076142132,
          "decode_step_ms": 8.0, "device_idle": 18.04005833333333,
          "engine_step_ms": 9.049999999999999,
          "flash_decode_paged_roofline": 0.0037271403714356733,
          "host_pump_ms": 0.19999999999999937,
          "prefill_mfu": 0.016056230891950688}
MERGED_BUSY_S = 0.039340772
MERGED_BREAKDOWN = {
    "device_ops": [["while.12", 0.019999995],
                   ["flash_decode_paged.13", 0.00894],
                   ["fusion.9", 0.005000777], ["fusion.1", 0.004],
                   ["copy.5", 0.0014]],
    "idle_gaps": [["outside client spans", 0.013999223],
                  ["engine_step", 0.002830005], ["fanout", 0.00283],
                  ["admission", 0.001]]}


def one_plane():
    """60 ms: two decode steps (each two layers of the paged kernel and
    a fusion), an admission (prefill 20-41 ms, commit 41-47 ms), host
    pumps, engine steps and the admission span."""
    ops, mods = [], []
    for t in (0, 10):
        mods.append(("jit_run_k(7)", t * MS, (t + 8) * MS))
        ops += [("fusion.1", t * MS, (t + 2) * MS),
                ("flash_decode_paged.13 custom-call", (t + 2) * MS,
                 (t + 4) * MS + 300_000),
                ("copy.5", (t + 4) * MS + 300_000, (t + 5) * MS),
                ("flash_decode_paged.13 custom-call", (t + 5) * MS,
                 (t + 7) * MS + 170_000)]
    mods += [("jit_pre(3)", 20 * MS, 41 * MS),
             ("jit_admit_commit(4)", 41 * MS, 47 * MS)]
    ops += [("while.12", 20 * MS + 5, 40 * MS),
            ("fusion.9", 41 * MS, 46 * MS + 777)]
    host = [("bench.window", 0, 60 * MS),
            ("pump", 0, 9 * MS), ("engine_step", 0, 8 * MS + 500_000),
            ("fanout", 8 * MS + 500_000, 9 * MS),
            ("pump", 10 * MS, 19 * MS), ("engine_step", 10 * MS, 19 * MS),
            ("pump", 20 * MS, 48 * MS), ("engine_step", 20 * MS, 48 * MS),
            ("admission", 20 * MS, 47 * MS + 3)]
    steps = [{"active": 2, "reads": 300, "paged_reads": 200, "dt": 0.009,
              "prefill_tokens": 0},
             {"active": 3, "reads": 510, "paged_reads": 333, "dt": 0.0091,
              "prefill_tokens": 700}]
    pumps = [(0.009, 0.0085), (0.009, 0.009), (0.028, 0.0279)]
    return ops, mods, host, steps, pumps, [700]


def _run(trace, steps=(), pumps=(), admitted=(), chips=1):
    return RunData(cell=None, hf=HF, trace=trace, pumps=list(pumps),
                   steps=list(steps), admitted=list(admitted), peak=PEAK,
                   flops=flops, chips=chips)


def _metric_names():
    return sorted(os.path.basename(p)[:-3] for p in glob.glob(
        os.path.join(_tiny.ROOT, "bench", "metrics", "*.py"))
        if not p.endswith("__init__.py"))


@pytest.mark.parametrize("name", sorted(MERGED))
def test_one_device_reads_as_before(name):
    ops, mods, host, steps, pumps, adm = one_plane()
    run = _run(T.from_events(ops, mods, host), steps, pumps, adm)
    assert load_metric(name)(run) == MERGED[name]


def test_one_device_busy_and_breakdown_as_before():
    ops, mods, host, *_ = one_plane()
    tr = T.from_events(ops, mods, host)
    t0, t1 = T.window(tr)
    assert T.busy_ns(tr, t0, t1) / 1e9 == MERGED_BUSY_S
    assert T.breakdown(tr, t0, t1) == MERGED_BREAKDOWN


def test_collectives_read_nothing_on_one_device():
    ops, mods, host, steps, pumps, adm = one_plane()
    run = _run(T.from_events(ops, mods, host), steps, pumps, adm)
    assert load_metric("collective_ms")(run) is None
    assert "collective_ms" in _metric_names()


def quarters():
    """40 ms, one pump over all of it; device ``i`` is busy over [10i,
    10i + 10] ms only, so the union of the four is busy all the time and
    each device a quarter of it."""
    return T.from_planes(
        [([("fusion.1", 10 * i * MS, (10 * i + 10) * MS)], [])
         for i in range(4)],
        [("bench.window", 0, 40 * MS), ("pump", 0, 40 * MS)])


def four_planes():
    """40 ms. Device ``i`` runs the two decode programs [0, 8] and [10,
    18] ms and the prefill [30, 34] ms. Inside each decode program: an
    all-reduce as a start (0.5 ms) and a done (1 ms),
    a fusion that only reads an all-reduce's result, an all-gather of
    0.5 + 0.1i ms and an op whose HLO opcode is an all-to-all (0.2 ms);
    an all-reduce in the prefill is outside the decode programs."""
    planes = []
    for i in range(4):
        mods = [("jit_run_k(7)", 0, 8 * MS), ("jit_run_k(7)", 10 * MS,
                                              18 * MS),
                ("jit_pre(3)", 30 * MS, 34 * MS)]
        ops = []
        for t in (0, 10):
            ops += [
                ("all-reduce-start.1", (t + 1) * MS, (t + 1) * MS + MS // 2),
                ("all-reduce-done.1", (t + 3) * MS, (t + 4) * MS),
                ("fusion.2 %fusion.2 = bf16[8]{0} fusion(bf16[8]{0} "
                 "%all-reduce.3), kind=kLoop", (t + 4) * MS, (t + 6) * MS),
                ("_all-gather.7", (t + 6) * MS,
                 (t + 6) * MS + MS // 2 + i * MS // 10),
                ("custom.3 %custom.3 = f32[4]{0} all-to-all(f32[4]{0} %x)",
                 (t + 7) * MS, (t + 7) * MS + MS // 5)]
        ops.append(("all-reduce.9", 31 * MS, 32 * MS))
        planes.append((ops, mods))
    host = [("bench.window", 0, 40 * MS), ("pump", 0, 40 * MS)]
    return T.from_planes(planes, host)


def test_idle_is_the_mean_over_devices_not_their_union():
    tr = quarters()
    t0, t1 = T.window(tr)
    union = T.union([op for p in tr.planes for op in p.ops], t0, t1)
    assert T.covered(union, t0, t1) == 40 * MS      # the old reading
    assert T.busy_ns(tr, t0, t1) == pytest.approx(10 * MS)
    assert load_metric("device_idle")(_run(tr, chips=4)) == pytest.approx(
        75.0)


def test_program_times_are_per_device():
    run = _run(four_planes(), chips=4)
    assert load_metric("decode_step_ms")(run) == pytest.approx(8.0)
    assert run.module_time("jit_run_k") == (16 * MS, 2)


def test_collectives_of_the_decode_programs_per_step_and_device():
    # 0.5 + 1 + (0.5 + 0.1i) + 0.2 ms a step on device i; mean over i
    got = load_metric("collective_ms")(_run(four_planes(), chips=4))
    assert got == pytest.approx(2.2 + 0.1 * 1.5)


@pytest.mark.parametrize("label,want", [
    ("all-reduce.5", True), ("_all-reduce-start.2", True),
    ("%reduce-scatter.1", True), ("collective-permute-done.4", True),
    ("all-to-all", True),
    ("fusion.3 %fusion.3 = f32[2]{0} fusion(f32[2]{0} %all-gather.1)",
     False),
    ("fusion.3 %fusion.3 = f32[2]{0} all-gather-start(f32[1]{0} %p)", True),
    ("all-reduce-fusion.2", False), ("copy.1", False)])
def test_collectives_by_name_or_opcode(label, want):
    assert T.is_collective(label) is want


def test_breakdown_names_the_busiest_device():
    tr = quarters()
    # device 2 gets one more op: the busiest
    tr.planes[2].ops.append(("copy.8", 35 * MS, 39 * MS))
    t0, t1 = T.window(tr)
    b = T.breakdown(tr, t0, t1)
    assert b["device_ops"] == [["fusion.1", 0.01], ["copy.8", 0.004]]
    # its idle gaps: [0, 20] and [30, 35] and [39, 40] ms
    assert [g for _, g in b["idle_gaps"]] == [0.02, 0.005, 0.001]


def test_shares_of_a_peak_divide_by_the_cell_chips():
    ops, mods, host, steps, pumps, adm = one_plane()
    one = _run(T.from_events(ops, mods, host), steps, pumps, adm)
    four = _run(T.from_planes([(ops, mods)] * 4, host), steps, pumps, adm,
                chips=4)
    for name in ("decode_mfu", "prefill_mfu", "flash_decode_paged_roofline"):
        assert load_metric(name)(four) == pytest.approx(
            load_metric(name)(one) / 4)
    for name in ("decode_step_ms", "admission_ms", "device_idle"):
        assert load_metric(name)(four) == pytest.approx(
            load_metric(name)(one))
