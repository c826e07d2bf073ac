"""Tiny copies of the hybrid linear-attention routed-FFN cell's files for
CPU rehearsals (``tiny.py`` for the cell
``glm-5.3-flash.longthink-backlog``). Never a device number from here."""

from chipbench.tests import tiny

CELL = "glm-5.3-flash.longthink-backlog"
MIXERS, MLPS = "KDKK", "dsss"


def config(compute_dtype="bfloat16"):
    out = tiny.load("configs", "glm-5.3-flash.json")
    out["program"]["config_kwargs"].update(
        vocab_size=96, d_model=64, mixers=MIXERS, mlps=MLPS, num_heads=4,
        head_dim=16, gate_rank=8, kda_chunk=4, mla_heads=4,
        qk_nope_head_dim=16, v_head_dim=16, q_lora_rank=32, kv_lora_rank=32,
        indexer_heads=4, indexer_head_dim=16, indexer_rope_dim=8,
        indexer_topk=12, dense_width=96, num_experts=16,
        experts_per_token=4, expert_width=32, shared_width=32,
        hc_sinkhorn_iters=6, held_experts=[4, 8], max_len=64)
    out["program"]["model_kwargs"].update(
        page_len=8, pages_per_seq=8, num_pages=40, max_live=4,
        decode_bucket_sizes=[1, 4], prefill_bucket_sizes=[1, 4])
    out["program"]["compute_dtype"] = compute_dtype
    spec = out["reference"]["spec"]
    spec.update(hidden=64, layers=len(MIXERS), mixers=MIXERS, mlps=MLPS,
                heads=4, head_dim=16, gate_rank=8, mla_heads=4, nope_dim=16,
                v_dim=16, q_rank=32, kv_rank=32, idx_heads=4, idx_dim=16,
                idx_rope_dim=8, idx_topk=12, dense_width=96, experts=16,
                experts_per_token=4, expert_width=32, shared_width=32,
                held=[4, 8], hc_iters=6, vocab=96, bias_std=0.3)
    if compute_dtype == "float32":
        spec["bf16_leaves"] = []
    return out


def mix():
    out = tiny.load("traffic", "longthink-backlog.json")
    out.update(prompt_len={"dist": "loguniform", "min": 14, "max": 40},
               output_len={"dist": "loguniform", "min": 4, "max": 16},
               drain_seconds=20.0, trace_seconds=1, trace_start_seconds=0,
               check_requests=4, block=8, max_rate_per_s=8.0, extra=4)
    return out


def measure(seed, compute_dtype="float32", seconds=1.5, limits=None):
    return tiny.measure(CELL, config(compute_dtype), mix(), seed,
                        seconds=seconds, limits=limits)
