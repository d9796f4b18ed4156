"""Random weights made on the device from the seed, in one jitted call.

The layout is the serving program's parameter tree, as the configuration's
family gives it (``bench/families/<model_type>.py``: ``shapes`` and
``leaf``); the reference reads the same tree, which the benchmark makes
and the program only receives. A sharded cell's tree is made in its
shardings, so that each device computes and holds only its own part.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from bench import families


def seed_key(seed: int) -> jax.Array:
    """A PRNG key from any non-negative seed, including seeds wider than
    32 bits."""
    seed = int(seed)
    if seed < 0:
        raise ValueError(f"seed must be non-negative, got {seed}")
    key = jax.random.PRNGKey(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, (seed >> 32) & 0xFFFFFFFF)


def shapes(hf: dict) -> dict:
    """Leaf shapes of the tree for config ``hf``."""
    return families.of(hf).shapes(hf)


def _build(key, hf_items):
    hf = dict(hf_items)
    fam = families.of(hf)
    dtype = jnp.dtype(hf["torch_dtype"])
    flat = []

    def walk(node, path):
        if isinstance(node, dict):
            return {k: walk(v, f"{path}/{k}") for k, v in node.items()}
        flat.append(path)
        return fam.leaf(jax.random.fold_in(key, len(flat)), path, node,
                        dtype)

    return walk(fam.shapes(hf), "")


def _items(hf: dict) -> tuple:
    return (("model_type", hf["model_type"]),) + tuple(
        (k, hf[k]) for k in families.of(hf).SIZE_KEYS)


def make_params(hf: dict, seed: int, shardings=None):
    """The whole tree for config ``hf`` from ``seed``, in the
    configuration's dtype: on the default device, or made in place in
    ``shardings`` (a tree of ``NamedSharding``s matching ``shapes``)."""
    make = jax.jit(functools.partial(_build, hf_items=_items(hf)),
                   out_shardings=shardings)
    return make(seed_key(seed))
