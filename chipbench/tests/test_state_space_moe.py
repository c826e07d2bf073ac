"""The hybrid state-space routed-FFN cell rehearsed on the CPU at tiny sizes
(``tiny_state_space_moe.py``): the new runner end to end, the control and
the planted fault coming out not ``correct``, the per-layer counters, the
metric patterns, and ``work_state_space_moe.py`` against a hand count."""

import json
import os

import numpy as np
import pytest

from chipbench import compare, work_state_space_moe as work
from chipbench.runners import serve_state_space_moe as runner
from chipbench.tests import tiny, tiny_state_space_moe

SEED = 3_000_000_035
TIGHT = {"logit_gap": {"limit": 1e-3}, "logprob_gap": {"limit": 1e-3},
         "logprob_gap_median": {"limit": 1e-4},
         "logprob_gap_p90": {"limit": 1e-4}, "missing": {"limit": 0}}
MODEL = "nemotron-3-nano-30b-a3b"


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
        line, result = tiny_state_space_moe.measure(SEED, limits=TIGHT)
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
    assert info["checked"]["longest"] >= 30
    assert info["first_fill_s"] is not None
    # no end token is served: every answer has the length it was given
    assert kept["eos_id"] == -1
    assert info["requests"]["ended_before_budget"] == 0
    # more requests than slots: slots are re-used; nothing is shared
    assert info["requests"]["finished"] > 4
    cache = info["prefix_cache"]
    assert cache["hit_pages"] == cache["cow_hits"] == 0
    assert cache["shared_pages"] == 0
    assert "SSMStateUpdate:interpret_backend" in \
        info["kernel_routing"]["fallback"]


def test_control_and_planted_token_are_not_correct(sound):
    _, _, kept = sound
    config, sample, eos_id = kept["config"], kept["sample"], kept["eos_id"]
    rows, missing = runner.check(config, SEED, sample, eos_id, control="fp8")
    assert missing == 0
    sound_numbers = runner.numbers_of(rows, missing)
    assert compare.is_correct(compare.against(sound_numbers, TIGHT))
    control = runner.numbers_of(rows, 0, control=True)
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
                                       tiny_state_space_moe.CELL)
    names = {entry["name"] for entry, _ in specs}
    assert {"ssm_update_ms.serve", "ssm_update_roofline",
            "ssm_scan_ms.serve", "state_bytes_share_pct", "prefill_pad_pct",
            "moe_local_pair_pct", "moe_load_imbalance", "mfu.serve",
            "decode_attn_ms.serve", "decode_fill_pct",
            "routed_ffn_ms.serve"} <= names
    assert not {"latent_attn_ms.serve", "sparse_selected_pct"} & names
    counters = {}
    for entry, spec in specs:
        if spec["reader"] == "counter":
            metric = spec["params"]["metric"]
            counters[metric] = ({"count": 0, "sum": 0.0},
                                harness.read_counter(metric, [MODEL]))
    got = harness.read_layer_metrics(
        [(e, s) for e, s in specs if s["reader"] == "counter"],
        {"counters": counters})
    # the tiny cut holds 8 of 16 experts: about half of the pairs
    assert 25.0 <= got["moe_local_pair_pct"]["value"] <= 75.0
    assert 1.0 <= got["moe_load_imbalance"]["value"] <= 8.0
    # tiny: 3 layers x (8 x 8 x 16 x 4 B + 3 x 96 x 4 B) of state a row
    # against a few pages of 2 x 2 x 16 x 4 B a token: most of the bytes
    assert 60.0 <= got["state_bytes_share_pct"]["value"] < 100.0
    # prompts of 6-40 tokens in pages of 8: some of every last chunk
    assert 0.0 < got["prefill_pad_pct"]["value"] < 60.0


@pytest.mark.parametrize("metric, hits", [
    ("ssm_update_ms.serve", {0}), ("ssm_update_roofline", {0}),
    ("decode_attn_ms.serve", {2}), ("routed_ffn_ms.serve", {4})])
def test_device_op_patterns(metric, hits):
    from chipbench import trace_reduce

    names = [
        "%stf_ssm_state_update_b256.16 = (f32[256,32,128]{2,1,0:T(8,128)S(1)}"
        ", f32[257,32,128,128]{3,2,1,0:T(8,128)}) custom-call(s32[256]{0} "
        "%p.1, s32[256]{0} %convert.2)",
        "%fusion.5 = f32[256,64,64]{2,1,0} fusion(f32[256,32,128]{2,1,0} "
        "%get-tuple-element.7)",
        "%stf_decode_attention_q1_paged.3 = bf16[256,2,1,16,128]{4,3,2,1,0} "
        "custom-call(s32[3328]{0} %bitcast.1, s32[256]{0} %p.2)",
        "%stf_decode_attention_q256_paged.1 = bf16[8,2,4,1024,128]"
        "{4,3,2,1,0} custom-call(",
        "%ragged-dot-none.7 = f32[768,1920]{1,0:T(8,128)} custom-call("
        "bf16[768,2688]{1,0} %fusion.9, bf16[64,2688,1920]{2,1,0} %p.3)",
        "%stf_latent_attention_q1_paged.4 = bf16[32,1,64,512]{3,2,1,0} "
        "custom-call("]
    events = [(name, 1000 * i, 10 ** i, "") for i, name in enumerate(names)]
    pattern = tiny.load("layer_metrics", metric + ".json")["params"]["pattern"]
    secs, n = trace_reduce.pattern_seconds(events, pattern)
    assert n == len(hits)
    assert secs == pytest.approx(sum(10 ** i for i in hits) / 1e9)


def test_a_program_without_the_counters_reports_none():
    """The parent commit has no ``state_bytes_share`` and no
    ``prefill_pad_share``: the reader then returns nothing and the line
    leaves the metric out."""
    from chipbench.readers import counter, kernel_ms, trace_events

    for name in ("state_bytes_share_pct", "prefill_pad_pct"):
        params = tiny.load("layer_metrics", name + ".json")["params"]
        assert counter.read(params, {"counters": {}}) is None
        assert counter.read(params, {"counters": {
            params["metric"]: (None, None)}}) is None
    roofline = tiny.load("layer_metrics", "ssm_update_roofline.json")
    assert trace_events.read(roofline["params"], {"work": {}}) is None
    for name in ("ssm_update_ms.serve", "ssm_scan_ms.serve"):
        params = tiny.load("layer_metrics", name + ".json")["params"]
        assert kernel_ms.read(params, {}) is None


def test_work_against_a_hand_count():
    with open(os.path.join(tiny.BENCH, "configs",
                           "nemotron-3-nano-30b-a3b.json")) as f:
        spec = json.load(f)["reference"]["spec"]
    assert [work.count(spec, k) for k in "ME*"] == [6, 5, 2]
    # in 2688 x (4096 + 6144 + 64) + out 4096 x 2688 + 4 taps x 6144
    assert work.mamba_params(spec) == 27_697_152 + 11_010_048 + 24_576
    # 6 FLOPs a state element: 64 x 64 x 128
    assert work.state_update_flops(spec) == 6 * 524_288 == 3_145_728
    # the row's float32 state in and out, three 4096-lane rows and two
    # (128, 8) column tiles, float32
    assert work.state_update_bytes(spec) == (
        2 * 2_097_152 + 4 * (3 * 4096 + 2 * 1024)) == 4_251_648
    # router 2688 x 128 + shared 2 x 2688 x 3712 + 6 x 64/128 = 3 held pairs
    assert work.ffn_params(spec) == (
        344_064 + 19_955_712 + 3 * 2 * 2688 * 1856)
    # q and o 2688 x 4096 each, k and v 2688 x 256 each
    assert work.attention_params(spec) == 2 * 11_010_048 + 2 * 688_128
    per_position = (6 * (2 * 38_731_776 + 3_145_728)
                    + 5 * 2 * 50_233_344 + 2 * 2 * 23_396_352)
    head = 2 * 2688 * 65536
    # a decode position at context 1,200: 4 x 32 x 128 a row, 2 layers
    assert work.decode_flops(spec, 1200) == (
        per_position + 2 * 16_384 * 1200 + head)
    for n in (7, 256, 1000):
        assert work.prompt_flops(spec, n) == pytest.approx(
            per_position * n + 2 * 16_384 * n * (n + 1) / 2, rel=1e-12)


def test_traced_work_counts_tokens_prompt_shares_and_kernel_rows():
    spec = tiny_state_space_moe.config()["reference"]["spec"]

    def request(n, submitted, times):
        req = runner._Request({"due": 0.0, "prompt": np.arange(2, 2 + n),
                               "max_new_tokens": 3})
        req.submitted, req.times = submitted, times
        return req

    first = request(20, 0.75, [1.0, 2.0, 3.0])
    got = runner.traced_work(spec, [first], 0.5, 2.5)
    assert got["decode_tokens"] == 2 and got["prompts"] == 1
    assert got["model_flops"] == (
        work.prompt_flops(spec, 19) + work.decode_flops(spec, 20)
        + work.decode_flops(spec, 21))
    flops, bytes_ = got["ssm_state_update"]
    # two tokens, each one row of one call in each of the 3 M layers
    assert flops == 2 * 3 * work.state_update_flops(spec)
    assert bytes_ == 2 * 3 * work.state_update_bytes(spec)
    joiner = request(30, 0.76, [2.8, 3.0])
    both = runner.traced_work(spec, [first, joiner], 0.5, 2.5)
    assert both["prompts"] == pytest.approx(1 + 0.5 / 0.8)
    assert both["ssm_state_update"] == got["ssm_state_update"]
