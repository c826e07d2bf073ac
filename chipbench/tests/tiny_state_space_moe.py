"""Tiny copies of the hybrid state-space routed-FFN cell's files for CPU
rehearsals (``tiny.py`` for the cell
``nemotron-3-nano-30b-a3b.reasoning-backlog``). Never a device number from
here."""

from chipbench.tests import tiny

CELL = "nemotron-3-nano-30b-a3b.reasoning-backlog"
PATTERN = "MEM*EM"


def config(compute_dtype="bfloat16"):
    out = tiny.load("configs", "nemotron-3-nano-30b-a3b.json")
    out["program"]["config_kwargs"].update(
        vocab_size=96, d_model=64, layer_pattern=PATTERN, num_heads=8,
        num_kv_heads=2, head_dim=16, mamba_num_heads=8, mamba_head_dim=8,
        ssm_state_size=16, n_groups=2, chunk_size=4, num_experts=16,
        experts_per_token=4, expert_width=32, shared_width=48,
        held_experts=[4, 8], max_len=64)
    out["program"]["model_kwargs"].update(
        page_len=8, pages_per_seq=8, num_pages=40, max_live=4,
        decode_bucket_sizes=[1, 4], prefill_bucket_sizes=[1, 4])
    out["program"]["compute_dtype"] = compute_dtype
    spec = out["reference"]["spec"]
    spec.update(hidden=64, layers=len(PATTERN), pattern=PATTERN, heads=8,
                kv_heads=2, head_dim=16, mamba_heads=8, mamba_head_dim=8,
                state=16, groups=2, vocab=96, experts=16,
                experts_per_token=4, expert_width=32, shared_width=48,
                held=[4, 8], bias_std=0.3, dt_range=[0.01, 0.5])
    if compute_dtype == "float32":
        spec["bf16_leaves"] = []
    return out


def mix():
    out = tiny.load("traffic", "reasoning-backlog.json")
    out.update(prompt_len={"dist": "loguniform", "min": 6, "max": 40},
               output_len={"dist": "loguniform", "min": 4, "max": 16},
               drain_seconds=20.0, trace_seconds=1, trace_start_seconds=0,
               check_requests=4, block=8, max_rate_per_s=8.0, extra=4)
    return out


def measure(seed, compute_dtype="float32", seconds=1.5, limits=None):
    return tiny.measure(CELL, config(compute_dtype), mix(), seed,
                        seconds=seconds, limits=limits)
