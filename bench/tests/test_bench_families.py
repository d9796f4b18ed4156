"""The Qwen3 family file gives exactly what the benchmark's inline Qwen3
mapping gave (kept here as numbers), and a configuration of an unknown
family fails naming the file it looked for."""

import json
import os

import _tiny
import pytest

from bench import families, flops, spec, weights


def _cfg(name):
    with open(os.path.join(_tiny.ROOT, "bench", "configs",
                           name + ".json")) as f:
        return json.load(f)


def _tree(n_layers, d, h, kv, dh, f, vocab, tied):
    L = (n_layers,)
    tree = {"embed": (vocab, d), "final_norm": (d,),
            "layers": {"ln1": L + (d,), "ln2": L + (d,),
                       "attn": {"wq": L + (d, h * dh), "wk": L + (d, kv * dh),
                                "wv": L + (d, kv * dh), "wo": L + (h * dh, d),
                                "q_norm": L + (dh,), "k_norm": L + (dh,)},
                       "mlp": {"gate": L + (d, f), "up": L + (d, f),
                               "down": L + (f, d)}}}
    if not tied:
        tree["lm_head"] = (d, vocab)
    return tree


# (ModelConfig fields, leaf shapes, [layer_weights, head_weights,
# attention_flops_per_token, kv_bytes_per_token, weight_bytes,
# decode_flops(16 active, 12,345 read), prefill_flops(1,024)])
INLINE = {
    "qwen3-0.6b": (
        dict(family="dense", n_layers=28, d_model=1024, n_heads=16,
             n_kv_heads=8, d_ff=3072, vocab=151936, d_head=128,
             qk_norm=True, rope_theta=1e6, rms_eps=1e-6,
             tie_embeddings=True, dtype="bfloat16"),
        _tree(28, 1024, 16, 8, 128, 3072, 151936, True),
        [15728640, 155582464, 229376, 114688, 1192099840, 21903147008,
         1022630821888]),
    "qwen3-14b-tp4": (
        dict(family="dense", n_layers=40, d_model=5120, n_heads=40,
             n_kv_heads=8, d_ff=17408, vocab=151936, d_head=128,
             qk_norm=True, rope_theta=1e6, rms_eps=1e-6,
             tie_embeddings=False, dtype="bfloat16"),
        _tree(40, 5120, 40, 8, 128, 17408, 151936, False),
        [330301440, 777912320, 819200, 163840, 29536614400, 457792061440,
         27489765949440]),
}


@pytest.mark.parametrize("name", sorted(INLINE))
def test_family_gives_the_inline_model_config(name):
    from repro.models.config import ModelConfig
    fields, _, _ = INLINE[name]
    assert spec.model_config(_cfg(name), name) == ModelConfig(name=name,
                                                               **fields)


@pytest.mark.parametrize("name", sorted(INLINE))
def test_family_gives_the_inline_leaf_shapes(name):
    assert weights.shapes(_cfg(name)) == INLINE[name][1]


@pytest.mark.parametrize("name", sorted(INLINE))
def test_family_gives_the_inline_counts(name):
    hf = _cfg(name)
    got = [flops.layer_weights(hf), flops.head_weights(hf),
           flops.attention_flops_per_token(hf), flops.kv_bytes_per_token(hf),
           flops.weight_bytes(hf), flops.decode_flops(hf, 16, 12345),
           flops.prefill_flops(hf, 1024)]
    assert got == INLINE[name][2]


def test_family_is_found_by_model_type():
    assert families.of(_cfg("qwen3-14b-tp4")).__name__ == "bench.families.qwen3"


@pytest.mark.parametrize("call", ["model_config", "shapes", "flops"])
def test_an_unknown_family_names_the_file_it_looked_for(call):
    hf = dict(_cfg("qwen3-0.6b"), model_type="mamba9")
    want = os.path.join(_tiny.ROOT, "bench", "families", "mamba9.py")
    with pytest.raises(ValueError, match="mamba9") as e:
        {"model_config": lambda: spec.model_config(hf, "x"),
         "shapes": lambda: weights.shapes(hf),
         "flops": lambda: flops.layer_weights(hf)}[call]()
    assert want in str(e.value)
