"""Operations and bytes of the served model's work, from shapes alone.

Counts are of what the algorithm needs: a multiply-add is 2 operations,
K and V are read once per layer in the configuration's dtype, and
padding is never counted.
"""

from __future__ import annotations

DTYPE_BYTES = {"bfloat16": 2, "float16": 2, "float32": 4}


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


def decode_flops(hf: dict, active: int, participating: int) -> int:
    """One decode step: ``active`` sequences through every layer and the
    head, attending to ``participating`` cached tokens in all (summed over
    the sequences)."""
    n_layers = hf["num_hidden_layers"]
    return (2 * active * (n_layers * layer_weights(hf) + head_weights(hf))
            + participating * attention_flops_per_token(hf))


def prefill_flops(hf: dict, prompt_len: int) -> int:
    """Prefill of one prompt: every token through every layer, causal
    attention over the prompt, and the head for the last token."""
    n_layers = hf["num_hidden_layers"]
    pairs = prompt_len * (prompt_len + 1) // 2
    return (2 * prompt_len * n_layers * layer_weights(hf)
            + pairs * attention_flops_per_token(hf)
            + 2 * head_weights(hf))


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
