"""Qwen3 (dense GQA with a per-head RMSNorm on queries and keys), as in the
published ``config.json`` of Qwen/Qwen3-0.6B and Qwen/Qwen3-14B."""

from __future__ import annotations

import jax
import jax.numpy as jnp

# the configuration keys the weight tree depends on
SIZE_KEYS = ("hidden_size", "intermediate_size", "num_attention_heads",
             "num_key_value_heads", "head_dim", "num_hidden_layers",
             "vocab_size", "tie_word_embeddings", "torch_dtype")

DTYPE_BYTES = {"bfloat16": 2, "float16": 2, "float32": 4}


def model_config(hf: dict, name: str):
    """The serving program's ``ModelConfig`` for a Qwen3 config file."""
    from repro.models.config import ModelConfig
    return ModelConfig(
        name=name, family="dense", n_layers=hf["num_hidden_layers"],
        d_model=hf["hidden_size"], n_heads=hf["num_attention_heads"],
        n_kv_heads=hf["num_key_value_heads"], d_ff=hf["intermediate_size"],
        vocab=hf["vocab_size"], d_head=hf["head_dim"], qk_norm=True,
        rope_theta=float(hf["rope_theta"]), rms_eps=float(hf["rms_norm_eps"]),
        tie_embeddings=bool(hf["tie_word_embeddings"]),
        dtype=hf["torch_dtype"])


# ------------------------------------------------------------------ weights
def shapes(hf: dict) -> dict:
    """Leaf shapes of the tree (the serving program's layout: layers
    stacked on a leading axis)."""
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


def leaf(key, path: str, shape: tuple, dtype):
    """Unit norms; normal(0, 0.02) embedding and head; normal(0, 1/fan_in)
    linears."""
    name = path.rsplit("/", 1)[-1]
    if name in ("ln1", "ln2", "final_norm", "q_norm", "k_norm"):
        return jnp.ones(shape, dtype)
    std = 0.02 if name in ("embed", "lm_head") else shape[-2] ** -0.5
    return (jax.random.normal(key, shape, dtype) * jnp.asarray(std, dtype))


# ----------------------------------------------------------- shape counts
def _dims(hf: dict):
    return (hf["hidden_size"], hf["intermediate_size"],
            hf["num_attention_heads"], hf["num_key_value_heads"],
            hf["head_dim"], hf["num_hidden_layers"], hf["vocab_size"])


def layer_weights(hf: dict) -> int:
    """Weights one token multiplies through in one decoder layer."""
    d, f, h, kv, dh, _, _ = _dims(hf)
    return d * h * dh + 2 * d * kv * dh + h * dh * d + 3 * d * f


def head_weights(hf: dict) -> int:
    d, *_, vocab = _dims(hf)
    return d * vocab


def attention_flops_per_token(hf: dict) -> int:
    """QK^T and PV of one query against one cached token, all layers."""
    _, _, h, _, dh, n_layers, _ = _dims(hf)
    return 4 * h * dh * n_layers


def kv_bytes_per_token(hf: dict) -> int:
    """K and V of one token in all layers."""
    _, _, _, kv, dh, n_layers, _ = _dims(hf)
    return 2 * kv * dh * n_layers * DTYPE_BYTES[hf["torch_dtype"]]


def weight_bytes(hf: dict) -> int:
    n_layers = hf["num_hidden_layers"]
    d = hf["hidden_size"]
    n = n_layers * (layer_weights(hf) + 2 * d + 2 * hf["head_dim"]) \
        + hf["vocab_size"] * d * (1 if hf["tie_word_embeddings"] else 2) + d
    return n * DTYPE_BYTES[hf["torch_dtype"]]
