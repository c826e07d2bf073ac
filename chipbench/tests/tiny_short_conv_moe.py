"""Tiny copies of the short-convolution routed-FFN cell's files for CPU
rehearsals (``tiny.py`` for the cell ``lfm2-8b-a1b.gen-backlog``). Never a
device number from here."""

from chipbench.tests import tiny

CELL = "lfm2-8b-a1b.gen-backlog"
LAYER_TYPES = ["conv", "full_attention", "conv", "conv", "full_attention"]


def config(compute_dtype="bfloat16"):
    out = tiny.load("configs", "lfm2-8b-a1b.json")
    out["program"]["config_kwargs"].update(
        vocab_size=96, d_model=128, layer_types=LAYER_TYPES,
        num_dense_layers=1, num_heads=4, num_kv_heads=2, head_dim=64,
        dense_width=96, num_experts=8, experts_per_token=4, expert_width=32,
        max_len=64)
    out["program"]["model_kwargs"].update(
        page_len=8, pages_per_seq=8, num_pages=40, max_live=4,
        decode_bucket_sizes=[1, 4], prefill_bucket_sizes=[1, 4])
    out["program"]["compute_dtype"] = compute_dtype
    spec = out["reference"]["spec"]
    spec.update(hidden=128, layers=len(LAYER_TYPES), layer_types=LAYER_TYPES,
                dense_layers=1, heads=4, kv_heads=2, head_dim=64,
                dense_width=96, experts=8, experts_per_token=4,
                expert_width=32, vocab=96, bias_std=0.3)
    if compute_dtype == "float32":
        spec["bf16_leaves"] = []
    return out


def mix():
    out = tiny.load("traffic", "gen-backlog.json")
    out.update(prompt_len={"dist": "loguniform", "min": 8, "max": 30},
               output_len={"dist": "loguniform", "min": 4, "max": 16},
               drain_seconds=20.0, trace_seconds=1, trace_start_seconds=0,
               check_requests=4, block=8, max_rate_per_s=8.0, extra=4)
    return out


def measure(seed, compute_dtype="float32", seconds=1.5, limits=None):
    return tiny.measure(CELL, config(compute_dtype), mix(), seed,
                        seconds=seconds, limits=limits)
