"""Tiny copies of the latent-attention routed-FFN cell's files for CPU
rehearsals (``tiny.py`` for the cell ``kimi-k2.7-code.repo-backlog``).
Never a device number from here."""

from chipbench.tests import tiny

CELL = "kimi-k2.7-code.repo-backlog"


def config(compute_dtype="bfloat16"):
    out = tiny.load("configs", "kimi-k2.7-code.json")
    out["program"]["config_kwargs"].update(
        vocab_size=96, d_model=64, num_layers=3, num_heads=4,
        qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
        q_lora_rank=32, kv_lora_rank=24, rope_theta=100.0, rope_factor=4.0,
        rope_original_len=16, dense_layers=1, dense_width=96,
        num_experts=16, experts_per_token=4, expert_width=32,
        held_experts=[4, 8], max_len=64)
    out["program"]["model_kwargs"].update(
        page_len=8, pages_per_seq=8, num_pages=40, max_live=4,
        decode_bucket_sizes=[1, 4], prefill_bucket_sizes=[1, 4])
    out["program"]["compute_dtype"] = compute_dtype
    spec = out["reference"]["spec"]
    spec.update(hidden=64, layers=3, dense_layers=1, heads=4, qk_nope_dim=16,
                qk_rope_dim=8, v_dim=16, q_rank=32, kv_rank=24, vocab=96,
                rope_theta=100.0, dense_width=96, experts=16,
                experts_per_token=4, expert_width=32, shared_width=32,
                held=[4, 8], latent_row=128, bias_std=0.3)
    spec["yarn"].update(factor=4.0, original_len=16)
    if compute_dtype == "float32":
        spec["bf16_leaves"] = []
    return out


def mix():
    out = tiny.load("traffic", "repo-backlog.json")
    out.update(prompt_len={"dist": "loguniform", "min": 12, "max": 44},
               output_len={"dist": "loguniform", "min": 4, "max": 16},
               drain_seconds=20.0, trace_seconds=1, trace_start_seconds=0,
               check_requests=4, block=8, max_rate_per_s=8.0, extra=4)
    return out


def measure(seed, compute_dtype="float32", seconds=1.5, limits=None):
    return tiny.measure(CELL, config(compute_dtype), mix(), seed,
                        seconds=seconds, limits=limits)
