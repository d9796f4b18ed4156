"""Random weights made on the device from the seed, in one jitted call.

The layout is the serving program's parameter tree (layers stacked on a
leading axis); the reference reads the same tree, which the benchmark
makes and the program only receives.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp


def seed_key(seed: int) -> jax.Array:
    """A PRNG key from any non-negative seed, including seeds wider than
    32 bits."""
    seed = int(seed)
    if seed < 0:
        raise ValueError(f"seed must be non-negative, got {seed}")
    key = jax.random.PRNGKey(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, (seed >> 32) & 0xFFFFFFFF)


def shapes(hf: dict) -> dict:
    """Leaf shapes of the tree for a Qwen3 config file."""
    d, f = hf["hidden_size"], hf["intermediate_size"]
    h, kv, dh = (hf["num_attention_heads"], hf["num_key_value_heads"],
                 hf["head_dim"])
    n_layers, vocab = hf["num_hidden_layers"], hf["vocab_size"]
    L = (n_layers,)
    tree = {
        "embed": (vocab, d),
        "final_norm": (d,),
        "layers": {
            "ln1": L + (d,), "ln2": L + (d,),
            "attn": {"wq": L + (d, h * dh), "wk": L + (d, kv * dh),
                     "wv": L + (d, kv * dh), "wo": L + (h * dh, d),
                     "q_norm": L + (dh,), "k_norm": L + (dh,)},
            "mlp": {"gate": L + (d, f), "up": L + (d, f), "down": L + (f, d)},
        },
    }
    if not hf["tie_word_embeddings"]:
        tree["lm_head"] = (d, vocab)
    return tree


def _leaf(key, path: str, shape: tuple, dtype):
    name = path.rsplit("/", 1)[-1]
    if name in ("ln1", "ln2", "final_norm", "q_norm", "k_norm"):
        return jnp.ones(shape, dtype)
    std = 0.02 if name in ("embed", "lm_head") else shape[-2] ** -0.5
    return (jax.random.normal(key, shape, dtype) * jnp.asarray(std, dtype))


@functools.partial(jax.jit, static_argnames=("hf_items",))
def _make(key, hf_items):
    hf = dict(hf_items)
    dtype = jnp.dtype(hf["torch_dtype"])
    flat = []

    def walk(node, path):
        if isinstance(node, dict):
            return {k: walk(v, f"{path}/{k}") for k, v in node.items()}
        flat.append(path)
        return _leaf(jax.random.fold_in(key, len(flat)), path, node, dtype)

    return walk(shapes(hf), "")


_SIZE_KEYS = ("hidden_size", "intermediate_size", "num_attention_heads",
              "num_key_value_heads", "head_dim", "num_hidden_layers",
              "vocab_size", "tie_word_embeddings", "torch_dtype")


def make_params(hf: dict, seed: int):
    """The whole tree for config ``hf`` from ``seed``, on the default
    device, in the configuration's dtype."""
    items = tuple((k, hf[k]) for k in _SIZE_KEYS)
    return _make(seed_key(seed), items)
