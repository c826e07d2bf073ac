"""The hybrid linear-attention routed-FFN cell rehearsed on the CPU at tiny
sizes (``tiny_hybrid_linear_moe.py``): the new runner end to end through
``run.measure()``, the control and the planted fault coming out not
``correct``, the per-layer counters, the metric patterns, and
``work_hybrid_linear_moe.py`` against a hand count."""

import json
import os

import numpy as np
import pytest

from chipbench import compare, work_hybrid_linear_moe as work
from chipbench.runners import serve_hybrid_linear_moe as runner
from chipbench.runners.serve import _Request
from chipbench.runners.serve_sparse_moe import numbers_of
from chipbench.tests import tiny, tiny_hybrid_linear_moe

SEED = 3_000_000_039
TIGHT = {"logit_gap": {"limit": 1e-3}, "logprob_gap": {"limit": 1e-3},
         "logprob_gap_median": {"limit": 1e-4},
         "logprob_gap_p90": {"limit": 1e-4}, "missing": {"limit": 0}}
MODEL = "glm-5.3-flash"


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
        line, result = tiny_hybrid_linear_moe.measure(SEED, limits=TIGHT)
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
    # contexts past the tiny index_topk of 12: the pooled choice is made
    assert info["checked"]["longest"] >= 20
    assert kept["eos_id"] == -1
    assert info["requests"]["ended_before_budget"] == 0
    assert info["requests"]["finished"] > 4
    cache = info["prefix_cache"]
    assert cache["hit_pages"] == cache["cow_hits"] == 0
    assert cache["shared_pages"] == 0
    assert "KDAStateUpdate:interpret_backend" in \
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
                                       tiny_hybrid_linear_moe.CELL)
    names = {entry["name"] for entry, _ in specs}
    assert {"kda_update_ms.serve", "kda_update_roofline",
            "kda_scan_ms.serve", "latent_attn_ms.serve",
            "latent_attn_roofline", "state_bytes_share_pct",
            "sparse_selected_pct", "prefill_pad_pct", "moe_local_pair_pct",
            "moe_load_imbalance", "mfu.serve", "decode_fill_pct",
            "routed_ffn_ms.serve"} <= names
    assert not {"decode_attn_ms.serve", "ssm_update_ms.serve",
                "row_gather_ms.serve", "select_topk_ms.serve"} & names
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
    # 3 layers x (4 x 16 x 16 x 4 B + 3 x 192 x 4 B) of state a row
    # against a few pages of (32 x 4 B + 16 x 4 B / 4) a token
    assert 60.0 <= got["state_bytes_share_pct"]["value"] < 100.0
    # contexts of 15-56 positions and a budget of 9 to 12 of them
    assert 15.0 < got["sparse_selected_pct"]["value"] < 100.0
    assert 0.0 < got["prefill_pad_pct"]["value"] < 60.0


@pytest.mark.parametrize("metric, hits", [
    ("kda_update_ms.serve", {0}), ("kda_update_roofline", {0}),
    ("latent_attn_ms.serve", {2}), ("routed_ffn_ms.serve", {3}),
    ("kda_scan_ms.serve", {5, 6, 8}), ("index_select_ms.serve", {10, 11, 12}),
    ("latent_gather_ms.serve", {13})])
def test_device_op_patterns(metric, hits):
    from chipbench import trace_reduce

    names = [
        "%stf_kda_state_update_b128.3 = (f32[128,64,128]{2,1,0}, "
        "f32[129,64,128,128]{3,2,1,0:T(8,128)}) custom-call(s32[128]{0} "
        "%p.1, s32[128]{0} %convert.2)",
        "%fusion.5 = f32[128,64,128]{2,1,0} fusion(f32[128,64,128]{2,1,0} "
        "%get-tuple-element.7)",
        "%stf_latent_attention_q1_paged.4 = bf16[128,1,64,512]{3,2,1,0} "
        "custom-call(s32[512]{0} %iota.1, s32[128]{0} %p.2)",
        "%ragged-dot-none.7 = f32[1024,4096]{1,0:T(8,128)} custom-call("
        "bf16[1024,4096]{1,0} %fusion.9, bf16[36,4096,4096]{2,1,0} %p.3)",
        "%stf_ssm_state_update_b256.16 = (f32[256,32,128]{2,1,0}) "
        "custom-call(s32[256]{0} %p.1)",
        "%multiply_reduce_fusion.6 = f32[2048,4,16,16]{3,2,1,0:T(8,128)} "
        "fusion(f32[2048,4,16,128]{3,2,1,0} %copy.4)",
        "%convolution_subtract_fusion.2 = f32[512,128,128]{2,1,0} fusion("
        "f32[512,64,128]{2,1,0} %p.9)",
        # the rows' token-major q (4 x 512 tokens, 64 heads, 128): not the
        # scan's
        "%fusion.77 = f32[2048,64,128]{2,1,0} fusion(bf16[2048,24576] %p.2)",
        "%add.3 = f32[64,128,128]{2,1,0} add(f32[64,128,128]{2,1,0} %a, "
        "f32[64,128,128]{2,1,0} %b)",
        # a while loop's counter first: its body's ops are counted
        "%while.12 = (s32[]{:T(128)}, f32[2048,4,16,16]{3,2,1,0}) while(",
        # the decode step's pooled choice: the pooled keys gathered, the
        # scores' sort, the chosen positions' sort
        "%fusion.40 = f32[3200,128,128]{2,1,0} fusion(f32[3233,128,128] %p)",
        "%sort = (f32[128,3200]{0,1:T(8,128)}, s32[128,3200]{0,1}) sort(",
        "%sort.9 = (pred[128,2048]{1,0}, s32[128,2048]{1,0}) sort(%copy.8",
        # the selected latent rows
        "%fusion.14 = bf16[262144,512]{1,0:T(8,128)(2,1)} fusion(%f.37)",
        # an element of a tuple is no event of its own
        "%get-tuple-element.802 = s32[128,3200]{0,1} get-tuple-element("]
    events = [(name, 1000 * i, 10 ** i, "") for i, name in enumerate(names)]
    pattern = tiny.load("layer_metrics", metric + ".json")["params"]["pattern"]
    secs, n = trace_reduce.pattern_seconds(events, pattern)
    assert n == len(hits)
    assert secs == pytest.approx(sum(10 ** i for i in hits) / 1e9)


def test_a_program_without_the_kernels_reports_none():
    """The parent commit has no KDA kernel and no chunked scan: the readers
    then return nothing and the line leaves the metrics out."""
    from chipbench.readers import kernel_ms, trace_events

    roofline = tiny.load("layer_metrics", "kda_update_roofline.json")
    assert trace_events.read(roofline["params"], {"work": {}}) is None
    for name in ("kda_update_ms.serve", "index_select_ms.serve",
                 "latent_gather_ms.serve"):
        params = tiny.load("layer_metrics", name + ".json")["params"]
        assert kernel_ms.read(params, {}) is None
    from chipbench.readers import kernel_per_call_ms
    scan = tiny.load("layer_metrics", "kda_scan_ms.serve.json")
    assert scan["reader"] == "kernel_per_call_ms"
    assert kernel_per_call_ms.read(scan["params"], {}) is None
    decode_only = {"trace": {"window": (0, 10 ** 9), "ops": [
        ("%stf_kda_state_update_b128.3 = (f32[128,64,128]) custom-call(",
         10, 1000, "")]}}
    assert kernel_per_call_ms.read(scan["params"], decode_only) is None


def test_scan_time_is_counted_per_prefill_call_on_the_device():
    """The first fill's prefill calls run seconds after their host spans
    closed: kda_scan_ms.serve counts the calls by the one while loop of the
    DSA layer's pooled choice that each call has, bucket 4 or 1."""
    from chipbench.readers import kernel_per_call_ms

    params = tiny.load("layer_metrics", "kda_scan_ms.serve.json")["params"]
    call4 = ("%while.57 = (s32[]{:T(128)}, f32[4,512,3200]{1,2,0:T(8,128)"
             "S(1)}, s32[]{:T(128)}, f32[4,3200,128]{2,1,0}) while(%t.1)")
    call1 = ("%while.57 = (s32[]{:T(128)}, f32[1,512,3200]{1,2,0:T(8,128)"
             "S(1)}, s32[]{:T(128)}, f32[1,3200,128]{2,1,0}) while(%t.2)")
    scan = "%fusion.9 = f32[2048,16,256]{2,1,0:T(8,128)} fusion(%p.3)"
    ops = [(call4, 0, 5_000_000, ""), (scan, 6_000_000, 30_000_000, ""),
           (call1, 40_000_000, 2_000_000, ""),
           (scan, 50_000_000, 6_000_000, ""),
           # the GTE of the loop's scores is no event of a call
           ("%while.99 = f32[4,512,3200]{1,2,0} get-tuple-element(%w)",
            60_000_000, 1, "")]
    got = kernel_per_call_ms.read(
        params, {"trace": {"window": (0, 10 ** 9), "ops": ops}})
    assert got == pytest.approx(1000.0 * 0.036 / 2)


def test_work_against_a_hand_count():
    with open(os.path.join(tiny.BENCH, "configs",
                           "glm-5.3-flash.json")) as f:
        spec = json.load(f)["reference"]["spec"]
    assert [work.count(spec, k) for k in "KDds"] == [4, 1, 1, 4]
    # 7 FLOPs a state element: 64 x 128 x 128
    assert work.kda_update_flops(spec) == 7 * 1_048_576
    # the row's float32 state in and out, four (128, 64) columns, the v
    # row in and o out, float32
    assert work.kda_update_bytes(spec) == (
        2 * 4_194_304 + 4 * 6 * 8192) == 8_585_216
    # qkv 4096 x 24576, conv 4 x 24576, beta 4096 x 64, two rank-128
    # gates 2 x (4096 x 128 + 128 x 8192), out 8192 x 4096
    assert work.kda_params(spec) == (100_663_296 + 98_304 + 262_144
                                     + 2 * 1_572_864 + 33_554_432)
    # 4 streams: 16384 x 24 and (16 + 8) x 4096
    assert work.hyper_params(spec) == 393_216 + 98_304
    # dense 3 x 4096 x 12288; routed: router 4096 x 288, shared 3 x 4096
    # x 2048, 8 x 36 / 288 = 1 held pair of 3 x 4096 x 2048
    assert work.ffn_params(spec, "d") == 150_994_944
    assert work.ffn_params(spec, "s") == (1_179_648 + 25_165_824
                                          + 25_165_824)
    # read: all of a short context; past the budget the tail block's
    # positions <= t and 511 blocks of 4
    assert work.read_positions(spec, 1000) == 1000
    assert work.read_positions(spec, 3001) == 2044 + 1
    assert work.read_positions(spec, 3004) == 2044 + 4
    f, b = work.latent_attention_work(spec, 3004)
    assert f == 2 * 64 * 2 * 512 * 2048
    assert b == 2 * (4 * 512 * 512 + 2 * 64 * 512)
    for n in (7, 512, 3000):
        assert work.prompt_flops(spec, n) == pytest.approx(sum(
            work.decode_flops(spec, j + 1) - 2 * 4096 * 19360
            for j in range(n)), rel=1e-9)


def test_traced_work_counts_tokens_prompt_shares_and_kernel_rows():
    spec = tiny_hybrid_linear_moe.config()["reference"]["spec"]

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
    flops, bytes_ = got["kda_state_update"]
    # two tokens, each one row of one call in each of the 3 K layers
    assert flops == 2 * 3 * work.kda_update_flops(spec)
    assert bytes_ == 2 * 3 * work.kda_update_bytes(spec)
    latent = [sum(x) for x in zip(work.latent_attention_work(spec, 20),
                                  work.latent_attention_work(spec, 21))]
    assert got["latent_attention"] == latent
