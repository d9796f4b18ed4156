"""A toy four-chip cell on eight fake CPU devices, driven through the
benchmark's harness (the look for a chip skipped). Run as a script by
``test_bench_sharded.py``: the device count must be set before JAX is
imported. Prints one JSON line of named results.

- the weights are made sharded on the cell's four devices: no device
  holds the whole tree, and the engine takes them without a copy;
- a sound run is ``correct``, and its end-to-end metrics are there;
- a run whose sampled tokens are altered where they are produced is not.
"""

import json
import os
import sys

os.environ["XLA_FLAGS"] = ("--xla_force_host_platform_device_count=8 "
                           + os.environ.get("XLA_FLAGS", ""))
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import _tiny  # noqa: E402
import jax  # noqa: E402

jax.config.update("jax_platform_name", "cpu")

from bench import run as R  # noqa: E402

SEED = 5
TRAFFIC = dict(_tiny.TRAFFIC, serving=dict(_tiny.TRAFFIC["serving"],
                                           pool_blocks=31))


def cell():
    import dataclasses
    return dataclasses.replace(_tiny.cell(), chips=4, traffic=TRAFFIC)


def serve(c, seed):
    params = R.make_weights(c, seed)
    served, _, setup_s = R.serve_cell(c, params, seed, 2.0)
    checks, _ = R.check(c, lambda: params, served, seed)
    return params, served, setup_s, checks


def main():
    out = {"devices": jax.device_count()}
    c = cell()
    devices = R.cell_devices(c)
    params = R.make_weights(c, SEED)
    total = sum(x.nbytes for x in jax.tree.leaves(params))
    per = R.bytes_per_device(params)
    out["weights_devices"] = sorted(per)
    out["weights_largest_share"] = max(per.values()) / total
    engine, _ = R.build_server(c, params, devices)
    same = [a is b or a.unsafe_buffer_pointer() == b.unsafe_buffer_pointer()
            for a, b in zip(jax.tree.leaves(params),
                            jax.tree.leaves(engine.params))]
    out["engine_params_in_place"] = all(same)
    out["engine_shard"] = engine.shard
    del engine

    _, served, setup_s, checks = serve(c, SEED)
    out["sound_correct"] = R.is_correct(checks)
    out["sound_checks"] = {k: v["value"] for k, v in checks.items()}
    out["sound_metrics"] = sorted(R.end_to_end(c, served, setup_s))
    out["sound_finished"] = len(served.finished)

    from repro.serving import engine as eng
    real = eng._sample_tokens

    def altered(logits, *a, **k):
        return (real(logits, *a, **k) + 1) % logits.shape[-1]

    eng._sample_tokens = altered
    for fn in (eng._fused_decode_fn, eng._admit_commit_fn, eng._prefill_fn):
        fn.cache_clear()
    _, _, _, checks = serve(c, SEED + 1)
    out["altered_correct"] = R.is_correct(checks)
    out["altered_max_logit_gap"] = checks["max_logit_gap"]["value"]
    print(json.dumps(out), flush=True)


if __name__ == "__main__":
    main()
