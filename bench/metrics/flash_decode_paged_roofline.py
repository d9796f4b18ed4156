"""Least time the paged decode kernel's work needs, over its device
time. The work is the K/V of the participating tokens that PAM reads
from the paged pool (warm and cold tiers), at the configuration's dtype,
and their QK^T and PV operations; the least time is the larger of bytes
over peak bandwidth and operations over peak bf16 rate."""


def read(run):
    total, n = run.op_time("flash_decode_paged")
    steps = [s for s in run.steps if s["active"] > 0]
    if not n or not steps:
        return None
    paged = sum(s["paged_reads"] for s in steps)
    # kernel calls are one per layer per step; match steps by count
    paged *= (n / run.hf["num_hidden_layers"]) / len(steps)
    nbytes = paged * run.flops.kv_bytes_per_token(run.hf)
    ops = paged * run.flops.attention_flops_per_token(run.hf)
    least = max(nbytes / run.peak["hbm_bytes_per_s"],
                ops / run.peak["bf16_flops_per_s"])
    return 100.0 * least / (total / 1e9)
