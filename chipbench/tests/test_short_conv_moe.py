"""The short-convolution routed-FFN cell rehearsed on the CPU at tiny sizes
(``tiny_short_conv_moe.py``): the runner end to end through
``run.measure()``, the control and the planted fault coming out not
``correct``, the per-layer counters, the metric patterns, and
``work_short_conv_moe.py`` against a hand count."""

import json
import os

import numpy as np
import pytest

from chipbench import compare, work_short_conv_moe as work
from chipbench.runners import serve_short_conv_moe as runner
from chipbench.runners.serve import _Request
from chipbench.runners.serve_sparse_moe import numbers_of
from chipbench.tests import tiny, tiny_short_conv_moe

SEED = 3_000_000_042
TIGHT = {"logit_gap": {"limit": 1e-3}, "logprob_gap": {"limit": 1e-3},
         "logprob_gap_median": {"limit": 1e-4},
         "logprob_gap_p90": {"limit": 1e-4}, "missing": {"limit": 0}}
MODEL = "lfm2-8b-a1b"


@pytest.fixture(scope="module")
def sound():
    """One float32 run through run.measure, and what it finished."""
    kept = {}
    real_check = runner.check

    def keep(config, seed, sample, eos_id, control=None, **kw):
        kept.update(config=config, sample=sample, eos_id=eos_id)
        return real_check(config, seed, sample, eos_id, control, **kw)

    runner.check = keep
    try:
        line, result = tiny_short_conv_moe.measure(SEED, limits=TIGHT)
    finally:
        runner.check = real_check
    return line, result, kept


def test_sound_run_is_correct(sound):
    line, result, kept = sound
    assert line["correct"] is True, line["compared"]
    assert line["attempted"] > 4 and line["failed"] == 0
    assert set(line["metrics"]) == {"serve_tokens_per_s", "setup_s"}
    info = result["info"]
    assert info["window_compiles"]["compiles"] == 0
    assert info["checked"]["requests"] == 4
    assert kept["eos_id"] == -1
    assert info["requests"]["ended_before_budget"] == 0
    assert info["requests"]["finished"] > 4
    cache = info["prefix_cache"]
    assert cache["hit_pages"] == cache["cow_hits"] == 0
    assert cache["shared_pages"] == 0
    assert "ShortConvUpdate:interpret_backend" in \
        info["kernel_routing"]["fallback"]


def test_control_and_planted_token_are_not_correct(sound):
    _, _, kept = sound
    config, sample, eos_id = kept["config"], kept["sample"], kept["eos_id"]
    rows, missing = runner.check(config, SEED, sample, eos_id, control="fp8")
    assert missing == 0
    sound_numbers = numbers_of(rows, missing)
    assert compare.is_correct(compare.against(sound_numbers, TIGHT))
    control = numbers_of(rows, 0, control=True)
    for tail in ("logprob_gap_median", "logprob_gap_p90"):
        assert control[tail] > 100 * sound_numbers[tail]
    assert not compare.is_correct(compare.against(control, TIGHT)), control
    fault, margins = runner.second_best_fault(config, SEED, sample, rows,
                                              eos_id)
    assert not compare.is_correct(compare.against(fault, TIGHT)), fault
    assert fault["logit_gap"] >= max(margins) - 1e-4 > 0


def test_counters_are_read_for_the_cell(sound):
    """With --trace 1 the manifest's counter metrics of the cell come back,
    from what the sound run sampled (the trace itself needs the chip: the
    readers are given a stub)."""
    from chipbench import harness

    specs = harness.layer_metric_specs(tiny.manifest(),
                                       tiny_short_conv_moe.CELL)
    names = {entry["name"] for entry, _ in specs}
    assert {"short_conv_ms.serve", "short_conv_roofline",
            "decode_attn_ms.serve", "routed_ffn_ms.serve",
            "state_bytes_share_pct", "prefill_pad_pct", "moe_load_imbalance",
            "mfu.serve", "decode_fill_pct", "sched_step_ms",
            "device_idle_pct.serve", "engine_admit_ms", "engine_self_ms",
            "session_self_ms.serve", "host_gap_ms.serve",
            "handover_return_ms.serve", "handover_launch_ms.serve",
            "host_serial_ms.serve", "model_host_ms.serve",
            "await_device_ms.serve"} == names
    counters = {}
    for entry, spec in specs:
        if spec["reader"] == "counter":
            metric = spec["params"]["metric"]
            counters[metric] = ({"count": 0, "sum": 0.0},
                                harness.read_counter(metric, [MODEL]))
    got = harness.read_layer_metrics(
        [(e, s) for e, s in specs if s["reader"] == "counter"],
        {"counters": counters})
    assert 1.0 <= got["moe_load_imbalance"]["value"] <= 8.0
    # 3 conv layers x 2 x 128 x 4 B of windows a row against a few pages
    # of 2 attention layers x 2 x 2 x 64 x 4 B a token
    assert 5.0 <= got["state_bytes_share_pct"]["value"] < 80.0
    assert 0.0 < got["prefill_pad_pct"]["value"] < 60.0
    assert 0.0 < got["decode_fill_pct"]["value"] <= 100.0


@pytest.mark.parametrize("metric, hits", [
    ("short_conv_ms.serve", {0}), ("short_conv_roofline", {0}),
    ("decode_attn_ms.serve", {1}), ("routed_ffn_ms.serve", {2})])
def test_device_op_patterns(metric, hits):
    from chipbench import trace_reduce

    names = [
        "%stf_short_conv_update_b256.3 = (bf16[256,16,128]{2,1,0}, "
        "bf16[257,32,128]{2,1,0:T(16,128)(2,1)}) custom-call(s32[256]{0} "
        "%p.1, s32[256]{0} %select.2)",
        "%stf_decode_attention_q1_paged_gqa.4 = bf16[256,4,1,8,128]{4,3,2,"
        "1,0} custom-call(s32[3328]{0} %p.1, s32[256]{0} %p.2)",
        "%ragged-dot-none.7 = f32[1024,3584]{1,0:T(8,128)} custom-call("
        "bf16[1024,2048]{1,0} %fusion.9, bf16[32,2048,3584]{2,1,0} %p.3)",
        "%stf_decode_attention_q256_paged_gqa.2 = bf16[8,4,2,1024,128] "
        "custom-call(s32[104]{0} %p.1)",
        "%fusion.5 = bf16[256,6144]{1,0} fusion(bf16[256,2048]{1,0} %p.7)",
        # an element of a tuple is no event of its own
        "%get-tuple-element.802 = bf16[257,32,128]{2,1,0} "
        "get-tuple-element(%stf_short_conv_update_b256.3)"]
    events = [(name, 1000 * i, 10 ** i, "") for i, name in enumerate(names)]
    pattern = tiny.load("layer_metrics", metric + ".json")["params"]["pattern"]
    secs, n = trace_reduce.pattern_seconds(events, pattern)
    assert n == len(hits)
    assert secs == pytest.approx(sum(10 ** i for i in hits) / 1e9)


def test_a_program_without_the_kernel_reports_none():
    """The parent commit has no short-conv kernel: the readers then return
    nothing and the line leaves the metrics out."""
    from chipbench.readers import kernel_ms, trace_events

    roofline = tiny.load("layer_metrics", "short_conv_roofline.json")
    assert trace_events.read(roofline["params"], {"work": {}}) is None
    params = tiny.load("layer_metrics", "short_conv_ms.serve.json")["params"]
    assert kernel_ms.read(params, {}) is None
    no_kernel = {"trace": {"window": (0, 10 ** 9), "ops": [
        ("%fusion.5 = bf16[256,6144]{1,0} fusion(%p.7)", 10, 1000, "")]},
        "work": {"short_conv_update": [1.0, 1.0]},
        "peak": tiny.load("peaks.json")["TPU v5 lite"]}
    assert trace_events.read(roofline["params"], no_kernel) is None


def test_work_against_a_hand_count():
    with open(os.path.join(tiny.BENCH, "configs", "lfm2-8b-a1b.json")) as f:
        spec = json.load(f)["reference"]["spec"]
    assert [work.count(spec, k) for k in ("conv", "full_attention")] == [
        10, 3]
    # W_in 2048 x 6144, W_out 2048 x 2048, 3 taps x 2048
    assert work.conv_params(spec) == 16_783_360
    # q and o 2048 x 2048, k and v 2048 x 512
    assert work.attention_params(spec) == 10_485_760
    # dense 3 x 2048 x 7168; routed: router 2048 x 32 and 4 of 32
    # experts of 3 x 2048 x 1792
    assert work.ffn_params(spec, True) == 44_040_192
    assert work.ffn_params(spec, False) == 65_536 + 4 * 11_010_048
    # a row of a conv layer: the window in and out (2 x 8,192 B), [B|C|x~]
    # in (12,288 B) and y out (4,096 B); 7 FLOPs a channel
    assert work.short_conv_update_bytes(spec) == 32_768
    assert work.short_conv_update_flops(spec) == 7 * 2048
    for n in (7, 256, 1000):
        assert work.prompt_flops(spec, n) == pytest.approx(sum(
            work.decode_flops(spec, j + 1) - 2 * 2048 * 65536
            for j in range(n)), rel=1e-9)


def test_traced_work_counts_tokens_prompt_shares_and_kernel_rows():
    spec = tiny_short_conv_moe.config()["reference"]["spec"]

    def request(n, submitted, times):
        req = _Request({"due": 0.0, "prompt": np.arange(2, 2 + n),
                        "max_new_tokens": 3})
        req.submitted, req.times = submitted, times
        return req

    first = request(20, 0.75, [1.0, 2.0, 3.0])
    got = runner.traced_work(spec, [first], 0.5, 2.5)
    assert got["decode_tokens"] == 2 and got["prompts"] == 1
    assert got["model_flops"] == (
        work.prompt_flops(spec, 19) + work.decode_flops(spec, 20)
        + work.decode_flops(spec, 21))
    flops, bytes_ = got["short_conv_update"]
    # two tokens, each one row of one call in each of the 3 conv layers
    assert flops == 2 * 3 * work.short_conv_update_flops(spec)
    assert bytes_ == 2 * 3 * work.short_conv_update_bytes(spec)
