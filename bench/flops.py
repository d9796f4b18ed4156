"""Operations and bytes of the served model's work, from shapes alone.

Counts are of what the algorithm needs: a multiply-add is 2 operations,
K and V are read once per layer in the configuration's dtype, and
padding is never counted. The shape counts of one layer, the head, the
cache and the weights come from the configuration's family
(``bench/families/<model_type>.py``).
"""

from __future__ import annotations

from bench import families


def layer_weights(hf: dict) -> int:
    """Weights one token multiplies through in one decoder layer."""
    return families.of(hf).layer_weights(hf)


def head_weights(hf: dict) -> int:
    return families.of(hf).head_weights(hf)


def attention_flops_per_token(hf: dict) -> int:
    """QK^T and PV of one query against one cached token, all layers."""
    return families.of(hf).attention_flops_per_token(hf)


def kv_bytes_per_token(hf: dict) -> int:
    """K and V of one token in all layers."""
    return families.of(hf).kv_bytes_per_token(hf)


def weight_bytes(hf: dict) -> int:
    return families.of(hf).weight_bytes(hf)


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
