"""The latent-attention routed-FFN cell rehearsed on the CPU at tiny sizes
(``tiny_latent_moe.py``): the new runner end to end, the control and the
planted fault coming out not ``correct``, the per-layer counters, the
metric patterns, and ``work_latent_moe.py`` against a hand count."""

import json
import os

import numpy as np
import pytest

from chipbench import compare, work_latent_moe as work
from chipbench.runners import serve_latent_moe as runner
from chipbench.tests import tiny, tiny_latent_moe

SEED = 3_000_000_033
TIGHT = {"logit_gap": {"limit": 1e-3}, "logprob_gap": {"limit": 1e-3},
         "logprob_gap_median": {"limit": 1e-4},
         "logprob_gap_p90": {"limit": 1e-4}, "missing": {"limit": 0}}


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
        line, result = tiny_latent_moe.measure(SEED, limits=TIGHT)
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
    assert info["checked"]["longest"] >= 40
    assert info["first_fill_s"] is not None
    # no end token is served: every answer has the length it was given
    assert kept["eos_id"] == -1
    assert info["requests"]["ended_before_budget"] == 0
    assert "PagedLatentAttention:interpret_backend" in \
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


def test_counters_are_read_for_the_cell():
    """With --trace 1 the manifest's counter metrics of the cell come back
    (the trace itself needs the chip: the readers are given a stub)."""
    from chipbench import harness

    specs = harness.layer_metric_specs(tiny.manifest(), tiny_latent_moe.CELL)
    names = {entry["name"] for entry, _ in specs}
    assert {"moe_local_pair_pct", "moe_load_imbalance", "mfu.serve",
            "latent_attn_ms.serve", "latent_attn_roofline",
            "decode_fill_pct", "routed_ffn_ms.serve"} <= names
    assert not {"decode_attn_ms.serve", "sparse_selected_pct"} & names
    counters = {}
    for entry, spec in specs:
        if spec["reader"] == "counter":
            metric = spec["params"]["metric"]
            counters[metric] = ({"count": 0, "sum": 0.0},
                                harness.read_counter(metric,
                                                     ["kimi-k2.7-code"]))
    got = harness.read_layer_metrics(
        [(e, s) for e, s in specs if s["reader"] == "counter"],
        {"counters": counters})
    # the tiny cut holds 8 of 16 experts: about half of the pairs
    assert 25.0 <= got["moe_local_pair_pct"]["value"] <= 75.0
    assert 1.0 <= got["moe_load_imbalance"]["value"] <= 8.0


@pytest.mark.parametrize("metric, hits", [
    ("latent_attn_ms.serve", {0}), ("latent_attn_roofline", {0}),
    ("routed_ffn_ms.serve", {2, 3})])
def test_device_op_patterns(metric, hits):
    from chipbench import trace_reduce

    names = [
        "%stf_latent_attention_q1_paged.3 = bf16[32,1,64,512]{3,2,1,0} "
        "custom-call(s32[1184]{0} %bitcast.1, s32[32]{0} %p.2)",
        "%stf_latent_attention_q512_paged.1 = bf16[4,64,512,512]{3,2,1,0} "
        "custom-call(",
        "%ragged-dot-none.7 = f32[256,4096]{1,0:T(8,128)} custom-call("
        "bf16[256,7168]{1,0} %fusion.9, bf16[12,7168,4096]{2,1,0} %p.3)",
        "%ragged-dot-none.12 = f32[16,7168]{1,0:T(8,128)} custom-call(",
        "%fusion.5 = bf16[32,64,512]{2,1,0} fusion(bf16[32,1,64,512]{3,2,1,0}"
        " %stf_latent_attention_q1_paged.3)",
        "%stf_decode_attention_q1_paged.4 = bf16[96,1,1024]{2,1,0} "
        "custom-call("]
    events = [(name, 1000 * i, 10 ** i, "") for i, name in enumerate(names)]
    pattern = tiny.load("layer_metrics", metric + ".json")["params"]["pattern"]
    secs, n = trace_reduce.pattern_seconds(events, pattern)
    assert n == len(hits)
    assert secs == pytest.approx(sum(10 ** i for i in hits) / 1e9)


def test_a_program_without_the_counter_reports_none():
    """The parent commit has no ``moe_local_pair_share``: the reader then
    returns nothing and the line leaves the metric out."""
    from chipbench import harness
    from chipbench.readers import counter, trace_events

    params = tiny.load("layer_metrics", "moe_local_pair_pct.json")["params"]
    assert harness.read_counter("/stf/serving/no_such_metric", ["m"]) is None
    assert counter.read(params, {"counters": {}}) is None
    assert counter.read(params, {"counters": {
        params["metric"]: (None, None)}}) is None
    roofline = tiny.load("layer_metrics", "latent_attn_roofline.json")
    assert trace_events.read(roofline["params"], {"work": {}}) is None


def test_work_against_a_hand_count():
    with open(os.path.join(tiny.BENCH, "configs",
                           "kimi-k2.7-code.json")) as f:
        spec = json.load(f)["reference"]["spec"]
    # q_a 7168x1536 + q_b 1536x12288 + kv_a 7168x576 + o 8192x7168
    assert work._attention_params(spec) == 92_733_440
    # W_kvb 512 x 64 x 256, 2 FLOPs a weight
    assert work._up_projection_flops(spec) == 16_777_216
    assert work.ffn_params(spec, True) == 3 * 7168 * 18432
    # router 7168x384 + shared 3x7168x2048 + 8 x 12/384 = 0.25 held pairs
    assert work.ffn_params(spec, False) == (
        2_752_512 + 44_040_192 + 0.25 * 44_040_192)
    per_position = (6 * (2 * 92_733_440 + 16_777_216)
                    + 2 * 396_361_728 + 5 * 2 * 57_802_752)
    head = 2 * 7168 * 20480
    # a decode position at context 10,000: 2 x 64 x (512 + 64 + 512) a row
    assert work.decode_attention_flops(spec, 10_000) == 1_392_640_000
    assert work.decode_flops(spec, 10_000) == (
        per_position + 6 * 1_392_640_000 + head)
    # a prompt in the plain form: 2 x 64 x (128 + 64 + 128) a key
    for n in (7, 512, 5000):
        assert work.prompt_flops(spec, n) == pytest.approx(
            per_position * n + 6 * 40_960 * n * (n + 1) / 2, rel=1e-12)
    # a page as stored: 512 rows x 640 lanes x 2 B
    assert work.page_bytes(spec, 512) == 655_360
    assert work.decode_attention_bytes(spec, 513, 512) == (
        2 * 655_360 + 64 * (640 + 512) * 2)
    assert work.cache_bytes_per_token(spec) == 7_680


def test_traced_work_counts_tokens_prompt_shares_and_kernel_calls():
    spec = tiny_latent_moe.config()["reference"]["spec"]

    def request(n, submitted, times):
        req = runner._Request({"due": 0.0, "prompt": np.arange(2, 2 + n),
                               "max_new_tokens": 3})
        req.submitted, req.times = submitted, times
        return req

    first = request(20, 0.75, [1.0, 2.0, 3.0])
    got = runner.traced_work(spec, 8, [first], 0.5, 2.5)
    assert got["decode_tokens"] == 2 and got["prompts"] == 1
    assert got["model_flops"] == (
        work.prompt_flops(spec, 19) + work.decode_flops(spec, 20)
        + work.decode_flops(spec, 21))
    flops, bytes_ = got["latent_attention"]
    assert flops == 3 * (work.decode_attention_flops(spec, 20)
                         + work.decode_attention_flops(spec, 21))
    # contexts 20 and 21 are 3 live pages of 8 rows each
    assert bytes_ == 3 * 2 * (3 * work.page_bytes(spec, 8)
                              + 4 * (128 + 24) * 2)
    joiner = request(30, 0.76, [2.8, 3.0])
    both = runner.traced_work(spec, 8, [first, joiner], 0.5, 2.5)
    assert both["prompts"] == pytest.approx(1 + 0.5 / 0.8)
    assert both["latent_attention"] == got["latent_attention"]
