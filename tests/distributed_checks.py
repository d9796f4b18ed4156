"""Multi-device distributed checks — executed by test_distributed.py in a
subprocess with XLA_FLAGS=--xla_force_host_platform_device_count=8 (set
BEFORE jax import, which is why this is a standalone script).

Checks:
  1. sequence-sharded PAMattention (shard_map psum merge) == dense oracle
  2. gather-based baseline == dense oracle (and is the comm-heavy variant)
  3. sharded train_step runs on a (2 dp, 4 tp) mesh and matches the
     single-device loss
  4. pipeline-parallel forward == sequential stage application
  5. elastic restore: checkpoint saved from mesh A restores onto mesh B
"""
import os

os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
from jax.sharding import AxisType, Mesh, NamedSharding, PartitionSpec as P  # noqa: E402

jax.config.update("jax_platform_name", "cpu")


from repro.distributed.pam_shard import (  # noqa: E402
    make_gather_based_decode_attn, make_sequence_sharded_decode_attn)
from repro.distributed.pipeline import (pipeline_apply,  # noqa: E402
                                        stages_from_layers)
from repro.distributed import sharding as shd  # noqa: E402
from repro.models.attention import dense_decode_attn  # noqa: E402
from repro.models import transformer as tf  # noqa: E402
from repro.models.config import get_config, reduced  # noqa: E402
from repro.checkpoint import save_pytree, restore_pytree  # noqa: E402

assert jax.device_count() == 8, jax.device_count()


def _auto_mesh(shape, names):
    """A mesh whose axes GSPMD partitions on its own (``jax.make_mesh``
    makes Explicit axes by default)."""
    return jax.make_mesh(shape, names,
                         axis_types=(AxisType.Auto,) * len(names))


def check_pam_shard_map():
    mesh = _auto_mesh((8,), ("model",))
    key = jax.random.PRNGKey(0)
    B, H, Hkv, S, dh = 2, 8, 4, 64, 16
    q = jax.random.normal(jax.random.fold_in(key, 0), (B, H, dh))
    k = jax.random.normal(jax.random.fold_in(key, 1), (B, Hkv, S, dh))
    v = jax.random.normal(jax.random.fold_in(key, 2), (B, Hkv, S, dh))
    lens = jnp.array([50, 17], jnp.int32)

    want_out, want_mass = dense_decode_attn(q, k, v, lens)

    with jax.set_mesh(mesh):
        seq_fn = make_sequence_sharded_decode_attn(mesh)
        out, mass = jax.jit(seq_fn)(q, k, v, lens)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want_out),
                               rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(np.asarray(mass), np.asarray(want_mass),
                               rtol=2e-4, atol=2e-5)

    with jax.set_mesh(mesh):
        gat_fn = make_gather_based_decode_attn(mesh)
        out2, _ = jax.jit(gat_fn)(q, k, v, lens)
    np.testing.assert_allclose(np.asarray(out2), np.asarray(want_out),
                               rtol=2e-5, atol=2e-5)

    # collective-bytes claim: the sequence-sharded form must move less
    with jax.set_mesh(mesh):
        _seq_hlo = jax.jit(seq_fn).lower(q, k, v, lens).compile().as_text()
        gat_hlo = jax.jit(gat_fn).lower(q, k, v, lens).compile().as_text()
    assert gat_hlo.count("all-gather") > 0
    print("  pam shard_map OK")


def check_fused_update_decode():
    """§Perf pam_shard_decode path: masked local cache write + psum merge
    == unsharded scatter + dense attention."""
    from repro.distributed.pam_shard import fused_update_decode
    mesh = _auto_mesh((8,), ("model",))
    key = jax.random.PRNGKey(4)
    B, H, Hkv, S, dh = 2, 8, 4, 64, 16
    q = jax.random.normal(jax.random.fold_in(key, 0), (B, H, dh))
    k = jax.random.normal(jax.random.fold_in(key, 1), (B, Hkv, S, dh))
    v = jax.random.normal(jax.random.fold_in(key, 2), (B, Hkv, S, dh))
    kn = jax.random.normal(jax.random.fold_in(key, 3), (B, Hkv, dh))
    vn = jax.random.normal(jax.random.fold_in(key, 4), (B, Hkv, dh))
    lens = jnp.array([37, 5], jnp.int32)   # different shards own the write

    bidx = jnp.arange(B)
    k_ref = k.at[bidx, :, lens].set(kn)
    v_ref = v.at[bidx, :, lens].set(vn)
    want_out, want_mass = dense_decode_attn(q, k_ref, v_ref, lens + 1)

    with jax.set_mesh(mesh):
        out, mass, kc, vc = jax.jit(
            lambda *a: fused_update_decode(*a))(q, k, v, kn, vn, lens)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want_out),
                               rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(np.asarray(kc), np.asarray(k_ref),
                               rtol=1e-6, atol=1e-6)
    np.testing.assert_allclose(np.asarray(mass), np.asarray(want_mass),
                               rtol=2e-4, atol=2e-5)
    print("  fused update+decode OK")


def check_sharded_train_step():
    from repro.training.train_step import TrainConfig, build_train_step, \
        init_train_state
    from repro.training import optim
    cfg = reduced(get_config("qwen3-0.6b"))
    tcfg = TrainConfig(adamw=optim.AdamWConfig(lr=1e-3))
    mesh = _auto_mesh((2, 4), ("data", "model"))

    state = init_train_state(cfg, tcfg, jax.random.PRNGKey(0))
    key = jax.random.PRNGKey(1)
    batch = {"tokens": jax.random.randint(key, (4, 32), 0, cfg.vocab),
             "labels": jax.random.randint(key, (4, 32), 0, cfg.vocab)}
    step = build_train_step(cfg, tcfg)
    _, m_ref = jax.jit(step)(state, batch)

    pspecs = shd.param_specs(cfg, mesh)
    ospecs = shd.opt_state_specs(cfg, mesh)
    bspecs = shd.batch_specs(cfg, 4, mesh)
    from repro.training.train_step import TrainState
    from repro.training.optim import AdamWState
    _state_specs = TrainState(   # spec pytree must CONSTRUCT
        params=pspecs,
        opt=AdamWState(step=P(), mu=ospecs, nu=ospecs),
        error_feedback=None)

    def put(tree, specs):
        return jax.tree.map(
            lambda x, s: jax.device_put(x, NamedSharding(mesh, s)),
            tree, specs, is_leaf=lambda x: isinstance(x, P))

    with jax.set_mesh(mesh):
        state_s = TrainState(
            params=put(state.params, pspecs),
            opt=AdamWState(step=state.opt.step,
                           mu=put(state.opt.mu, ospecs),
                           nu=put(state.opt.nu, ospecs)),
            error_feedback=None)
        batch_s = {k2: jax.device_put(v, NamedSharding(mesh, bspecs[k2]))
                   for k2, v in batch.items()}
        sharded_step = jax.jit(step)
        new_state, m = sharded_step(state_s, batch_s)
    np.testing.assert_allclose(float(m["loss"]), float(m_ref["loss"]),
                               rtol=1e-4)
    # params stayed sharded
    wq = new_state.params["layers"]["attn"]["wq"]
    assert not isinstance(wq.sharding, jax.sharding.SingleDeviceSharding)
    print("  sharded train_step OK")


def check_pipeline():
    mesh = _auto_mesh((8,), ("stage",))
    L, d = 8, 16
    key = jax.random.PRNGKey(3)
    ws = jax.random.normal(key, (L, d, d)) * 0.3
    layer_params = {"w": ws}

    def stage_fn(params, x):   # applies my group of layers
        def body(h, w):
            return jnp.tanh(h @ w), None
        out, _ = jax.lax.scan(body, x, params["w"])
        return out

    M, mb = 4, 2
    xs = jax.random.normal(jax.random.fold_in(key, 1), (M, mb, d))

    # sequential oracle
    def seq(x):
        for i in range(L):
            x = jnp.tanh(x @ ws[i])
        return x
    want = jax.vmap(seq)(xs.reshape(M * mb, d)).reshape(M, mb, d)

    stacked = stages_from_layers(layer_params, 8)
    with jax.set_mesh(mesh):
        run = pipeline_apply(mesh, stage_fn, 8)
        got = run(stacked, xs)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)
    print("  pipeline OK")


def check_elastic_restore(tmpdir="/tmp/elastic_ck"):
    cfg = reduced(get_config("qwen3-0.6b"))
    params = tf.init_params(cfg, jax.random.PRNGKey(7))
    mesh_a = _auto_mesh((2, 4), ("data", "model"))
    specs = shd.param_specs(cfg, mesh_a)
    params_a = jax.tree.map(
        lambda x, s: jax.device_put(x, NamedSharding(mesh_a, s)),
        params, specs, is_leaf=lambda x: isinstance(x, P))
    save_pytree(params_a, tmpdir)

    # "failure": restart on a smaller mesh (1 dp x 4 tp = 4 devices)
    from repro.distributed.elastic import plan_recovery
    kept, info = plan_recovery(jax.devices(), failed_hosts={1},
                               model_parallel=4, devices_per_host=4)
    assert info["new_dp"] == 1 and len(kept) == 4
    mesh_b = Mesh(np.asarray(kept).reshape(1, 4), ("data", "model"))
    specs_b = shd.param_specs(cfg, mesh_b)
    restored = restore_pytree(
        params, tmpdir,
        shardings=jax.tree.map(lambda s: NamedSharding(mesh_b, s), specs_b,
                               is_leaf=lambda x: isinstance(x, P)))
    for a, b in zip(jax.tree.leaves(params), jax.tree.leaves(restored)):
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(b, np.float32))
    print("  elastic restore OK")


if __name__ == "__main__":
    check_pam_shard_map()
    check_fused_update_decode()
    check_sharded_train_step()
    check_pipeline()
    check_elastic_restore()
    print("ALL DISTRIBUTED CHECKS PASSED")
