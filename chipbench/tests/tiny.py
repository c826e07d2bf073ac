"""Tiny copies of the cells' files for CPU rehearsals: the same files the
chip runs read, with the sizes cut. Never a device number from here."""

import copy
import json
import os
import time
import types

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)


def load(*parts):
    with open(os.path.join(BENCH, *parts)) as f:
        return json.load(f)


def manifest():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def bert(compute_dtype="bfloat16"):
    config = load("configs", "bert-base.json")
    config["program"]["config_kwargs"].update(
        vocab_size=99, hidden_size=32, num_layers=2, num_heads=2,
        intermediate_size=64, max_position=64)
    config["program"]["compute_dtype"] = compute_dtype
    spec = config["reference"]["spec"]
    spec.update(hidden=32, ffn=64, layers=2, heads=2, vocab=99,
                max_position=64)
    if compute_dtype == "float32":
        spec["bf16_leaves"] = []
    job = load("traffic", "pretrain-s512-b48.json")
    job.update(batch=4, seq_len=32, masked_per_row=5, short_min_tokens=12,
               pool_batches=4, trace_seconds=1)
    return config, job


def lm(compute_dtype="bfloat16"):
    config = load("configs", "lm-big.json")
    config["program"]["config_kwargs"].update(
        vocab_size=64, d_model=32, num_heads=2, d_ff=64, num_layers=2,
        max_len=64)
    config["program"]["model_kwargs"].update(
        page_len=8, pages_per_seq=8, num_pages=32, max_live=4,
        decode_bucket_sizes=[1, 2, 4], prefill_bucket_sizes=[1, 2, 4])
    config["program"]["compute_dtype"] = compute_dtype
    config["reference"]["max_output"] = 16
    spec = config["reference"]["spec"]
    spec.update(hidden=32, ffn=64, layers=2, heads=2, vocab=64)
    if compute_dtype == "float32":
        spec["bf16_leaves"] = []
    return config


def mix(name):
    out = load("traffic", name + ".json")
    out.update(prompt_len={"dist": "loguniform", "min": 8, "max": 40},
               output_len={"dist": "loguniform", "min": 4, "max": 16},
               drain_seconds=5.0, trace_seconds=1, trace_start_seconds=0,
               check_requests=4, block=8, max_rate_per_s=60.0, extra=4)
    return out


def measure(cell_name, config, traffic, seed, seconds=1.5, limits=None):
    """Everything of a run after the look for a chip, on the CPU's
    device: what run.py's main() does once it has found its TPU."""
    import jax
    from chipbench import run

    man = copy.deepcopy(manifest())
    cell = [w for w in man["workloads"] if w["name"] == cell_name][0]
    args = types.SimpleNamespace(workload=cell_name, seed=seed,
                                 seconds=seconds, trace=0)
    limits = limits or load("limits", cell_name + ".json")
    peak = load("peaks.json")["TPU v5 lite"]
    return run.measure(args, man, cell, config, traffic, limits,
                       jax.devices()[:1], peak, time.perf_counter())
