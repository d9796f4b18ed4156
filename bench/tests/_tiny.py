"""A tiny cell for CPU tests: the benchmark's harness at toy widths."""

import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
for _p in (ROOT, os.path.join(ROOT, "src")):
    if _p not in sys.path:
        sys.path.insert(0, _p)

HF = {"model_type": "qwen3", "hidden_size": 128, "intermediate_size": 256,
      "num_hidden_layers": 2, "num_attention_heads": 4,
      "num_key_value_heads": 2, "head_dim": 32, "vocab_size": 512,
      "rope_theta": 1000000, "rms_norm_eps": 1e-6,
      "tie_word_embeddings": True, "torch_dtype": "bfloat16",
      "reference": "qwen3"}

TRAFFIC = {
    "loop": "closed", "clients": 2, "requests": 8, "stratum": 4,
    "prompt": {"dist": "uniform", "lo": 40, "hi": 90},
    "answer": {"dist": "lognormal", "median": 12, "sigma": 0.5,
               "lo": 8, "hi": 24},
    "serving": {"max_batch": 4, "max_len": 128, "block_size": 16,
                "hot_window": 32, "pool_blocks": 32,
                "pam": {"hot_capacity": 16, "warm_capacity": 32,
                        "compression": 4, "recency_window": 8,
                        "schedule_interval": 2, "lam": 0.6}},
    "warm": {"64": [1, 2], "128": [1, 2]}, "grace_seconds": 30,
    "trace_seconds": 1, "reference_requests": 4, "reference_block": 2}

# Readings at this size on seeds 1-10 (CPU). The program's importance lay
# within 0.017 of the reference's mass-made share, so the reference
# compares the positions before a cut that 0.03 of the share leaves open.
# There the sound program's widest gap was at most 0.0021 and its mean
# 0.0001; the fp8 control's at least 0.038 and 0.0052.
LIMIT = 0.011
MEAN = 0.001
RTOL = 0.03

def cell(**traffic):
    from bench import spec
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    return spec.Cell(name="tiny", config_name="tiny", hf=dict(HF),
                     traffic_name="tiny", traffic=dict(TRAFFIC, **traffic),
                     limits={"max_logit_gap": LIMIT, "mean_logit_gap": MEAN,
                             "positions": 24, "importance_rtol": RTOL},
                     chips=1,
                     run_seconds=2, end_to_end=tuple(bench["end_to_end"]),
                     per_layer=tuple(bench["per_layer"]))
