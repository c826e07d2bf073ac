"""The sparse-attention routed-FFN cell rehearsed on the CPU at tiny sizes
(``tiny_sparse_moe.py``): the new runner end to end, the control and the
planted fault coming out not ``correct``, the per-layer counters, and
``work_sparse_moe.py`` against a hand count."""

import json
import os

import numpy as np
import pytest

from chipbench import compare, traffic, work_sparse_moe as work
from chipbench.runners import serve_sparse_moe as runner
from chipbench.tests import tiny, tiny_sparse_moe

SEED = 3_000_000_027
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
        line, result = tiny_sparse_moe.measure(SEED, limits=TIGHT)
    finally:
        runner.check = real_check
    return line, result, kept


def test_sound_run_is_correct(sound):
    line, result, _ = sound
    assert line["correct"] is True, line["compared"]
    assert line["attempted"] > 4 and line["failed"] == 0
    assert set(line["metrics"]) == {"serve_tokens_per_s", "setup_s"}
    info = result["info"]
    assert info["window_compiles"]["compiles"] == 0
    assert info["checked"]["requests"] == 4
    # the longest finished request is among the checked
    assert info["checked"]["longest"] >= 40
    assert info["first_fill_s"] is not None


def test_control_and_planted_token_are_not_correct(sound):
    _, _, kept = sound
    config, sample, eos_id = kept["config"], kept["sample"], kept["eos_id"]
    rows, missing = runner.check(config, SEED, sample, eos_id, control="fp8")
    assert missing == 0
    sound_numbers = runner.numbers_of(rows, missing)
    assert set(sound_numbers) == {"logit_gap", "logprob_gap", "missing",
                                  "logprob_gap_median", "logprob_gap_p90"}
    assert compare.is_correct(compare.against(sound_numbers, TIGHT))
    control = runner.numbers_of(rows, 0, control=True)
    for tail in ("logprob_gap_median", "logprob_gap_p90"):
        assert control[tail] > 100 * sound_numbers[tail]
    assert not compare.is_correct(compare.against(control, TIGHT)), control
    fault, margins = runner.second_best_fault(config, SEED, sample, rows,
                                              eos_id)
    assert not compare.is_correct(compare.against(fault, TIGHT)), fault
    # the altered place reads the reference's own margin there; the
    # tokens served after it, which went on from the server's own token,
    # read more
    assert fault["logit_gap"] >= max(margins) - 1e-4 > 0


def test_an_answer_cut_short_is_missing(sound):
    _, _, kept = sound
    sample = kept["sample"]
    cut = sample[1]
    tokens, cut.tokens = cut.tokens, cut.tokens[:-1]
    try:
        rows, missing = runner.check(kept["config"], SEED, sample,
                                     kept["eos_id"])
    finally:
        cut.tokens = tokens
    assert missing == 1 or tokens[-2] == kept["eos_id"]


def test_counters_are_read_for_the_cell():
    """With --trace 1 the manifest's counter metrics of the cell come back
    (the trace itself needs the chip: the readers are given a stub)."""
    from chipbench import harness

    manifest = tiny.manifest()
    specs = harness.layer_metric_specs(manifest, tiny_sparse_moe.CELL)
    names = {entry["name"] for entry, _ in specs}
    assert {"moe_load_imbalance", "sparse_selected_pct", "mfu.serve",
            "decode_fill_pct"} <= names
    assert "decode_attn_ms.serve" not in names   # that kernel is not called
    label = ["keye-vl2-30b-a3b"]
    counters = {}
    for entry, spec in specs:
        if spec["reader"] == "counter":
            metric = spec["params"]["metric"]
            value = harness.read_counter(metric, label)
            counters[metric] = ({"count": 0, "sum": 0.0}, value)
    got = harness.read_layer_metrics(
        [(e, s) for e, s in specs if s["reader"] == "counter"],
        {"counters": counters})
    assert 1.0 <= got["moe_load_imbalance"]["value"] <= 8.0
    assert 8.0 <= got["sparse_selected_pct"]["value"] <= 100.0


@pytest.mark.parametrize("metric, hits", [
    ("routed_ffn_ms.serve", {0, 1}), ("row_gather_ms.serve", {2}),
    ("select_topk_ms.serve", {3})])
def test_device_op_patterns(metric, hits):
    """The three XLA ops that carry the step, by the names the v5e gave
    them (``breakdown.device_ops`` of PR 27's traced run), and their
    neighbours that must not be counted."""
    from chipbench import trace_reduce

    names = [
        "%ragged-dot-none.7 = f32[4096,1536]{1,0:T(8,128)} custom-call("
        "bf16[4096,2048]{1,0} %fusion.9, bf16[128,2048,1536]{2,1,0} %p.3)",
        "%ragged-dot-none.12 = f32[128,2048]{1,0:T(8,128)} custom-call(",
        "%fusion.412 = bf16[32768,512]{1,0:T(8,128)(2,1)} fusion(",
        "%sort.3 = (f32[16,33792]{1,0:T(8,128)}, s32[16,33792]{1,0}) sort(",
        "%sort.9 = (s32[128]{0:T(128)}, s32[128]{0:T(128)}) sort(",
        "%fusion.5 = bf16[1056,512,64]{2,1,0} fusion(",
        "%fusion.6 = s32[32768]{0:T(1024)} fusion(",
        "%copy.2 = bf16[761,512,64]{2,1,0} copy(",
        "%stf_decode_attention_q1.4 = bf16[96,16,64]{2,1,0} custom-call("]
    events = [(name, 1000 * i, 10 ** i, "") for i, name in enumerate(names)]
    pattern = tiny.load("layer_metrics", metric + ".json")["params"]["pattern"]
    secs, n = trace_reduce.pattern_seconds(events, pattern)
    assert n == len(hits)
    assert secs == pytest.approx(sum(10 ** i for i in hits) / 1e9)


def test_a_program_without_the_counters_reports_none():
    from chipbench.readers import counter

    params = tiny.load("layer_metrics", "moe_load_imbalance.json")["params"]
    assert counter.read(params, {"counters": {}}) is None
    assert counter.read(params, {"counters": {
        params["metric"]: (None, None)}}) is None


def test_work_against_a_hand_count():
    with open(os.path.join(tiny.BENCH, "configs",
                           "keye-vl2-30b-a3b.json")) as f:
        spec = json.load(f)["reference"]["spec"]
    # attention 2*2048*4096 + 2*2048*512 = 18,874,368; indexer
    # 2048*(1024 + 64 + 16) = 2,260,992; router 2048*128 = 262,144;
    # 8 experts x 3*2048*768 = 37,748,736
    assert work.layer_matmul_params(spec) == 59_146_240
    # a position at context 10,000: indexer 2*16*64*10,000, attention over
    # the 2048 selected 4*4096*2048; head 2*2048*151,936
    layer = 2 * 59_146_240 + 20_480_000 + 33_554_432
    assert work.decode_flops(spec, 10_000) == 6 * layer + 622_329_856
    # under topk everything is attended
    assert work.decode_flops(spec, 100) == 6 * (
        2 * 59_146_240 + 204_800 + 1_638_400) + 622_329_856
    # a prompt is its positions summed, without the head
    for n in (7, 2048, 2049, 5000):
        each = sum(work.decode_flops(spec, c) - 622_329_856
                   for c in range(1, n + 1))
        assert work.prompt_flops(spec, n) == pytest.approx(each, rel=1e-12)
    assert work.cache_bytes_per_token(spec) == 13_056


def test_traced_work_counts_tokens_and_prompt_shares():
    spec = tiny_sparse_moe.config()["reference"]["spec"]

    def request(n, submitted, times):
        req = runner._Request({"due": 0.0, "prompt": np.arange(2, 2 + n),
                               "max_new_tokens": 3})
        req.submitted, req.times = submitted, times
        return req

    # an idle engine: the prompt is prefilled from its submission on
    first = request(20, 0.75, [1.0, 2.0, 3.0])
    got = runner.traced_work(spec, [first], 0.5, 2.5)
    assert got["decode_tokens"] == 2 and got["prompts"] == 1
    assert got["model_flops"] == (
        work.prompt_flops(spec, 19) + work.decode_flops(spec, 20)
        + work.decode_flops(spec, 21))
    # a joiner is prefilled between the step before (2.0) and its first
    # token (2.8): [2.0, 2.5] of it lies inside
    joiner = request(30, 0.76, [2.8, 3.0])
    both = runner.traced_work(spec, [first, joiner], 0.5, 2.5)
    assert both["prompts"] == pytest.approx(1 + 0.5 / 0.8)
    assert both["model_flops"] == pytest.approx(
        got["model_flops"] + 0.5 / 0.8 * work.prompt_flops(spec, 29))


def test_every_seed_offers_the_blocks_in_one_order():
    mix = tiny_sparse_moe.mix()

    def offered(seed):
        reqs = [runner._Request(r) for r in
                traffic.requests(mix, 96, seed, seconds=2.0)]
        return reqs, runner.in_one_order(reqs, mix)

    (made_a, a), (made_b, b) = offered(SEED), offered(SEED + 1)
    sizes = [(len(r.prompt), r.budget) for r in a]
    assert sizes == [(len(r.prompt), r.budget) for r in b]
    # the generator's own order is the seed's; the requests are its own
    assert [(len(r.prompt), r.budget) for r in made_a] != \
        [(len(r.prompt), r.budget) for r in made_b]
    assert sorted(map(id, a)) == sorted(map(id, made_a))
    block = mix["block"]
    assert len(a) > block and len(a) % block == 0
    for k in range(0, len(a), block):
        assert sorted(sizes[k:k + block]) == sorted(sizes[:block])
    # blocks differ in order, and the block's middle pair heads the queue
    assert sizes[:block] != sizes[block:2 * block]
    assert sizes[0] == sorted(sizes[:block])[block // 2]


def test_no_answer_ends_before_its_budget(sound):
    """The configuration serves no end token, so every finished answer
    has the length the traffic gave it."""
    _, result, kept = sound
    assert kept["eos_id"] == -1
    requests = result["info"]["requests"]
    assert requests["finished"] > 4
    assert requests["ended_before_budget"] == 0
