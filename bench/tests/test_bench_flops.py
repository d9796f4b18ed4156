"""Operation and byte counts of both configurations, worked by hand."""

import json
import os

import _tiny
from bench import flops


def _cfg(name):
    with open(os.path.join(_tiny.ROOT, "bench", "configs", name)) as f:
        return json.load(f)


Q06 = _cfg("qwen3-0.6b.json")
Q14 = _cfg("qwen3-14b-8L.json")


def test_layer_weights():
    # q 1024x2048, k and v 1024x1024 each, o 2048x1024, 3 x 1024x3072
    assert flops.layer_weights(Q06) == (2097152 + 2 * 1048576 + 2097152
                                        + 3 * 3145728)  # 15,728,640
    # q 5120x5120, k and v 5120x1024, o 5120x5120, 3 x 5120x17408
    assert flops.layer_weights(Q14) == (26214400 + 2 * 5242880 + 26214400
                                        + 3 * 89128960)  # 330,301,440


def test_kv_bytes_per_token():
    assert flops.kv_bytes_per_token(Q06) == 28 * 2 * 8 * 128 * 2   # 112 KiB
    assert flops.kv_bytes_per_token(Q06) == 112 * 1024
    assert flops.kv_bytes_per_token(Q14) == 32 * 1024


def test_weight_bytes():
    # 0.6b: 28 x (15.73M + norms) + tied 151936x1024 embedding + final norm
    n06 = 28 * (15728640 + 2048 + 256) + 151936 * 1024 + 1024
    assert flops.weight_bytes(Q06) == 2 * n06          # 1.19 GB
    n14 = 8 * (330301440 + 10240 + 256) + 2 * 151936 * 5120 + 5120
    assert flops.weight_bytes(Q14) == 2 * n14          # 8.47 GB


def test_decode_flops():
    # one sequence, 1000 participating tokens
    mm = 2 * (28 * 15728640 + 1024 * 151936)
    att = 1000 * 4 * 16 * 128 * 28
    assert flops.decode_flops(Q06, 1, 1000) == mm + att
    assert flops.attention_flops_per_token(Q14) == 4 * 40 * 128 * 8


def test_prefill_flops():
    p = 1024
    want = (2 * p * 8 * 330301440 + (p * (p + 1) // 2) * 4 * 40 * 128 * 8
            + 2 * 5120 * 151936)
    assert flops.prefill_flops(Q14, p) == want        # about 5.50 TFLOP
    assert 5.4e12 < want < 5.6e12
