"""Tiny copies of the sparse-attention routed-FFN cell's files for CPU
rehearsals (``tiny.py`` for the cell ``keye-vl2-30b-a3b.longdoc-backlog``).
Never a device number from here."""

from chipbench.tests import tiny

CELL = "keye-vl2-30b-a3b.longdoc-backlog"


def config(compute_dtype="bfloat16"):
    out = tiny.load("configs", "keye-vl2-30b-a3b.json")
    out["program"]["config_kwargs"].update(
        vocab_size=96, d_model=64, num_layers=2, num_heads=8, num_kv_heads=2,
        head_dim=16, num_experts=8, experts_per_token=2, expert_width=32,
        indexer_heads=4, indexer_head_dim=8, indexer_topk=8, max_len=64)
    out["program"]["model_kwargs"].update(
        page_len=8, pages_per_seq=8, num_pages=40, max_live=4,
        decode_bucket_sizes=[1, 4], prefill_bucket_sizes=[1, 4])
    out["program"]["compute_dtype"] = compute_dtype
    spec = out["reference"]["spec"]
    spec.update(hidden=64, layers=2, heads=8, kv_heads=2, head_dim=16,
                vocab=96, experts=8, experts_per_token=2, expert_width=32,
                indexer_heads=4, indexer_dim=8, topk=8)
    if compute_dtype == "float32":
        spec["bf16_leaves"] = []
    return out


def mix():
    out = tiny.load("traffic", "longdoc-backlog.json")
    out.update(prompt_len={"dist": "loguniform", "min": 12, "max": 44},
               output_len={"dist": "loguniform", "min": 4, "max": 16},
               drain_seconds=20.0, trace_seconds=1, trace_start_seconds=0,
               check_requests=4, block=8, max_rate_per_s=8.0, extra=4)
    return out


def measure(seed, compute_dtype="float32", seconds=1.5, limits=None):
    return tiny.measure(CELL, config(compute_dtype), mix(), seed,
                        seconds=seconds, limits=limits)
