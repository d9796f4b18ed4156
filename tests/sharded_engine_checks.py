"""Sharded-engine checks — executed by test_sharded_engine.py in a
subprocess with XLA_FLAGS=--xla_force_host_platform_device_count=8 (set
BEFORE jax import, which is why this is a standalone script).

The PR 10 acceptance bar: a shard-N engine built from the SAME
``EngineSpec`` (only ``shard`` differing) emits token streams
BIT-IDENTICAL to the unsharded engine — greedy, sampled, and with
``micro_steps=8`` — while keeping the 1-dispatch/step and donation
invariants; a request migrated mid-decode between engines of DIFFERENT
shard counts continues bit-exactly; and a 2-way replica group serves
from ~1/2 the param bytes per device that a full copy would take.

Checks:
  1. greedy twin exactness at shard 2 and 4 (+ dispatch/donation)
  2. sampled (temperature=1.0) twin exactness at shard 2
  3. micro_steps=8 twin exactness at shard 2
  4. mid-decode migration shard 2 -> shard 4 stays bit-exact (sampled)
  5. replica group: 2-way group param bytes <= 0.6x the full copy,
     cluster streams exact; from_cli round-trip forms the ISSUE's
     "hbm:1,cxl:2 --shard 2" topology; each group owns its devices
  6. ``chip_smoke.py --chips 4``'s body at reduced widths: four bf16
     one-device replicas on four devices and the float32 shard=4 engine,
     each token-identical to its one-device twin
"""
import dataclasses
import os
import sys

os.environ["XLA_FLAGS"] = ("--xla_force_host_platform_device_count=8 "
                           + os.environ.get("XLA_FLAGS", ""))

import jax  # noqa: E402
import numpy as np  # noqa: E402

jax.config.update("jax_platform_name", "cpu")

from repro.cluster.migration import KVSnapshot  # noqa: E402
from repro.cluster.spec import ClusterSpec  # noqa: E402
from repro.models import transformer as tf  # noqa: E402
from repro.models.config import get_config, reduced  # noqa: E402
from repro.perfmodel.devices import HBM_CLASS  # noqa: E402
from repro.serving.engine import Request, ServingConfig  # noqa: E402
from repro.serving.pam_manager import PAMManagerConfig  # noqa: E402
from repro.serving.spec import EngineSpec  # noqa: E402

assert jax.device_count() == 8, jax.device_count()

CFG = reduced(get_config("qwen3-0.6b"))
PARAMS = tf.init_params(CFG, jax.random.PRNGKey(0))
PAM = PAMManagerConfig(max_tokens=64, hot_capacity=8, warm_capacity=16,
                       compression=4, recency_window=4,
                       schedule_interval=2)
SCFG = ServingConfig(pam=PAM, max_batch=2, max_len=64, block_size=8,
                     pool_blocks=23, hot_window=16)


def requests(n=3, plen=20, max_new=10, seed=7):
    rng = np.random.default_rng(seed)
    return [Request(id=i + 1,
                    prompt=rng.integers(1, CFG.vocab, plen),
                    max_new_tokens=max_new) for i in range(n)]


def run(shard, scfg=SCFG, n=3):
    eng = EngineSpec(model=CFG, serving=scfg, shard=shard,
                     name=f"s{shard}").build(PARAMS)
    for r in requests(n):
        eng.submit(r)
    eng.run()
    return {rid: rs.outputs for rid, rs in eng.requests.items()}, eng


def check_greedy_twins_and_invariants():
    base, e1 = run(1)
    full_bytes = e1.params_bytes_per_device()
    for shard in (2, 4):
        got, eng = run(shard)
        assert got == base, f"shard {shard} diverged from unsharded"
        # 1 fused dispatch per device decode step, sharding included
        assert eng.decode_dispatches == eng.decode_device_steps
        assert eng.shard == shard
        assert eng.summary()["shard"] == shard
        # sharded params really occupy ~1/shard of a full copy
        per_dev = eng.params_bytes_per_device()
        assert per_dev <= 0.6 * full_bytes / (shard // 2 or 1), \
            (shard, per_dev, full_bytes)
    # donation: the sharded cache buffers are consumed by the fused
    # step, never copied (capture mid-run, confirm deleted at the end)
    eng = EngineSpec(model=CFG, serving=SCFG, shard=2,
                     name="don").build(PARAMS)
    for r in requests():
        eng.submit(r)
    for _ in range(4):
        eng.step()
    k_buf, pk_buf = eng.cache.k, eng.cache.pk
    tbl_buf = eng.pam_state.block_table
    eng.run()
    assert k_buf.is_deleted() and pk_buf.is_deleted()
    assert tbl_buf.is_deleted()
    print("1. greedy twins exact at shard 2/4; 1 dispatch/step; "
          f"donated; param bytes/device {full_bytes} -> "
          f"{per_dev} at shard 4")


def check_sampled_twins():
    scfg = dataclasses.replace(SCFG, temperature=1.0, sample_seed=11)
    base, _ = run(1, scfg)
    got, _ = run(2, scfg)
    assert got == base, "sampled shard-2 stream diverged"
    print("2. sampled (T=1.0) twins exact at shard 2")


def check_micro_twins():
    scfg = dataclasses.replace(SCFG, micro_steps=8)
    base, _ = run(1, scfg)
    got, eng = run(2, scfg)
    assert got == base, "micro_steps=8 shard-2 stream diverged"
    # the micro loop fuses several device steps into each dispatch
    # (the trailing dispatch runs fewer than 8 when the budget clips)
    assert eng.decode_device_steps > eng.decode_dispatches
    print("3. micro_steps=8 twins exact at shard 2")


def check_cross_shard_migration():
    scfg = dataclasses.replace(SCFG, temperature=1.0, sample_seed=5)
    base, _ = run(1, scfg)
    src = EngineSpec(model=CFG, serving=scfg, shard=2,
                     name="src").build(PARAMS)
    dst = EngineSpec(model=CFG, serving=scfg, shard=4,
                     name="dst").build(PARAMS)
    for r in requests(2):
        src.submit(r)
    for _ in range(4):                       # both mid-decode
        src.step()
    snap = KVSnapshot.export(src, 1)
    assert snap.src_shard == 2               # observability field
    assert snap.verify()
    snap.commit(dst)                         # 2-way ring -> 4-way ring
    src.run()
    dst.run()
    assert dst.requests[1].outputs == base[1], "migrated stream diverged"
    assert src.requests[2].outputs == base[2], "stay-behind diverged"
    print("4. mid-decode migration shard 2 -> 4 bit-exact (sampled)")


def check_replica_groups():
    base, e1 = run(1)
    full_bytes = e1.params_bytes_per_device()
    spec = ClusterSpec.of(CFG, [HBM_CLASS, HBM_CLASS], serving=SCFG,
                          shard=2)
    assert len(spec.groups) == 1 and spec.groups[0].devices == 2
    assert spec.physical_devices == 2
    router = spec.build(PARAMS)
    assert len(router.devices) == 1          # one engine per group
    eng = router.devices[0].engine
    assert eng.shard == 2
    assert eng.params_bytes_per_device() <= 0.6 * full_bytes
    for r in requests():
        router.submit(r)
    s = router.run()
    assert s["finished"] == 3
    for rid, rs in router.finished.items():
        assert rs.outputs == base[rid], rid

    # the ISSUE's launcher example: a lone hbm + one 2-way cxl group
    spec = ClusterSpec.from_cli("hbm:1,cxl:2", model=CFG, serving=SCFG,
                                shard=2)
    assert [g.devices for g in spec.groups] == [1, 2]
    assert spec.cli() == "hbm:1,cxl:2"       # round-trip
    # each replica group owns its own devices, in order
    held = [[d.id for d in dev.engine.devices]
            for dev in spec.build(PARAMS).devices]
    assert held == [[0], [1, 2]], held
    print(f"5. 2-way replica group: {eng.params_bytes_per_device()} "
          f"bytes/device vs {full_bytes} full copy; cluster streams "
          f"exact; hbm:1,cxl:2 --shard 2 forms [1, 2]-device groups")


def check_chip_smoke_fleet():
    import importlib.util
    path = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "chip_smoke.py")
    spec = importlib.util.spec_from_file_location("chip_smoke", path)
    smoke = importlib.util.module_from_spec(spec)
    sys.modules["chip_smoke"] = smoke
    spec.loader.exec_module(smoke)
    cfg = dataclasses.replace(CFG, dtype="bfloat16")
    shape = smoke.SmokeShape(requests=4, prompt_len=24, new_tokens=6,
                             max_len=64, block_size=8, hot_window=16)
    out = smoke.run_fleet(cfg, jax.devices()[:4], shape, seed=1)
    assert sorted(i for g in out["replicas"].values() for i in g) == [
        0, 1, 2, 3], out["replicas"]
    assert out["shard"] == [0, 1, 2, 3]
    print("6. chip_smoke --chips 4 body: replicas on devices 0-3 and "
          "shard=4 token-identical to their one-device twins")


if __name__ == "__main__":
    check_greedy_twins_and_invariants()
    check_sampled_twins()
    check_micro_twins()
    check_cross_shard_migration()
    check_replica_groups()
    check_chip_smoke_fleet()
    print("ALL SHARDED ENGINE CHECKS PASSED")
