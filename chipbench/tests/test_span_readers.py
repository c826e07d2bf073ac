"""The readers of the program's own spans (``stf/...``) and named kernels,
on a small hand-built trace (data/span_trace.json, times in ns): nested
spans, spans cut by the window, ``#k=v#`` suffixes, two host threads, and
nothing to read -> None, never 0."""

import json
import os

import pytest

from chipbench import trace_reduce as tr
from chipbench.readers import (_spans, host_gap_ms, kernel_ms, span_ms,
                               trace_events)

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)


def metric_params(name):
    with open(os.path.join(BENCH, "layer_metrics", name + ".json")) as f:
        return json.load(f)["params"]


@pytest.fixture(scope="module")
def facts():
    with open(os.path.join(HERE, "data", "span_trace.json")) as f:
        raw = json.load(f)
    raw["device"] = {k: [tuple(e) for e in v]
                     for k, v in raw["device"].items()}
    raw["host"] = [tuple(e) for e in raw["host"]]
    return {"trace": tr.reduce(raw, chips=1)}


@pytest.fixture(scope="module")
def parent_facts():
    """The same trace as a program without the spans or the kernel names
    would leave it: what the parent commit gives every new reader."""
    with open(os.path.join(HERE, "data", "small_trace.json")) as f:
        raw = json.load(f)
    raw["device"] = {k: [tuple(e) for e in v]
                     for k, v in raw["device"].items()}
    raw["host"] = [tuple(e) for e in raw["host"]]
    return {"trace": tr.reduce(raw, chips=1)}


def test_names_are_cut_at_the_hash_and_counted_whole(facts):
    trace = facts["trace"]
    spans = _spans.program_spans(trace)
    assert set(spans) == {"stf/engine/" + n for n in
                          ("step", "decode", "deliver", "admit",
                           "prefill")} | {
        "stf/session/" + n for n in ("run", "stage_feeds",
                                     "device_execute", "commit", "fetch")}
    # decode A starts before the window: two of three count as steps
    assert len(spans["stf/engine/decode"]) == 3
    assert len(_spans.whole(spans, "stf/engine/decode",
                            trace["window"])) == 2
    # the run cut by the window's end does not count, its inside part sums
    assert len(_spans.whole(spans, "stf/session/run", trace["window"])) == 1
    assert _spans.seconds(spans, ["stf/session/run"], trace["window"]) \
        == pytest.approx((3800 + 1000) / 1e9)


@pytest.mark.parametrize("metric, expected", [
    # admit 4000 over the 2 whole decode steps
    ("engine_admit_ms", 4000 / 2),
    # steps cut to the window 2000 + 5000 + 6000, decodes inside them
    # 1600 + 4000 + 5000
    ("engine_self_ms", (13000 - 10600) / 2),
    # runs 3800 + 1000 (cut), fetch inside a run of its own thread
    # 2000 + 500 (cut); the main thread's early fetch lies in no run
    ("session_self_ms.serve", (4800 - 2500) / 1),
    ("session_self_ms.train", (4800 - 2500) / 1),
    # two q1 events (the q64 call is the prefill's), per whole decode
    ("decode_attn_ms.serve", (2000 + 3000) / 2),
    # one flash event, per whole Session.run
    ("flash_attn_ms.train", 2000 / 1),
])
def test_span_and_kernel_readers(facts, parent_facts, metric, expected):
    spec = json.load(open(os.path.join(
        BENCH, "layer_metrics", metric + ".json")))
    reader = {"span_ms": span_ms, "kernel_ms": kernel_ms}[spec["reader"]]
    assert reader.read(spec["params"], facts) == \
        pytest.approx(expected / 1e6)
    assert reader.read(spec["params"], parent_facts) is None
    assert reader.read(spec["params"], {}) is None


def test_the_roofline_finds_its_kernels_by_their_names(facts, parent_facts):
    """The one flash event's 2000 ns, not the decode kernels and not the
    fusion that consumes a kernel's result; a trace whose kernels carry no
    name of ours has nothing to read."""
    params = metric_params("flash_attn_roofline")
    assert params["pattern"] == metric_params("flash_attn_ms.train")["pattern"]
    more = {"peak": {"flops_per_s_bf16": 1e12, "hbm_bytes_per_s": 1e11},
            "work": {"flash_attention": [1e3, 10.0]}}  # least time 1 ns
    assert trace_events.read(params, dict(facts, **more)) == \
        pytest.approx(100 * 1 / 2000)
    assert trace_events.read(params, dict(parent_facts, **more)) is None
    assert trace_events.read(params, facts) is None  # no work counted


def test_the_logged_parts_of_a_step_add_up(facts, capsys):
    span_ms.read(metric_params("engine_self_ms"), facts)
    logged = json.loads(capsys.readouterr().out)["span_ms"]
    assert logged["steps"] == 2
    assert logged["per_span_mean_ms"] == pytest.approx(4500 / 1e6)
    assert logged["window_ms_per_step"] == pytest.approx(10000 / 1e6)
    # steps and decodes reach from the window's start (step A is cut by
    # it) to step C's end at 18000
    assert logged["spanned_ms_per_step"] == pytest.approx(8500 / 1e6)
    assert logged["per_span_ms_per_step"] == pytest.approx(5300 / 1e6)
    # admission's own share: admit 4000 minus the prefill inside it 3000
    assert logged["also"]["admit_self_ms"] == pytest.approx(500 / 1e6)
    assert logged["also"]["deliver_ms"] == pytest.approx(750 / 1e6)
    assert logged["also"]["wait_ms"] == 0


def test_idle_gaps_go_to_the_innermost_program_span(facts, capsys):
    params = metric_params("host_gap_ms.serve")
    value = host_gap_ms.read(params, facts)
    logged = json.loads(capsys.readouterr().out)["host_gap"]
    gaps = logged["by_innermost_span_s"]
    # 3000-4000 and 6000-7500 under admit (the second past the prefill's
    # end), 11000-12500 deliver B, 15500-18000 decode C (narrower than its
    # step), 20000-21000 the main thread's fetch inside its run
    assert gaps == {
        "stf/engine/admit": pytest.approx(2500e-9),
        "stf/engine/deliver": pytest.approx(1500e-9),
        "stf/engine/decode": pytest.approx(2500e-9),
        "stf/session/fetch": pytest.approx(1000e-9)}
    assert logged["idle_s"] == pytest.approx(7500e-9)
    assert logged["under_no_span_share"] == 0
    assert value == pytest.approx(7500 / 2 / 1e6)
    # busy inside the window 12500, of which one q1 call (2000 + 3000: both
    # events share the instruction) and the fusion (1500) carry a scope
    assert logged["busy_with_stf_scope_share"] == \
        pytest.approx(6500 / 12500)
    assert host_gap_ms.read(metric_params("host_gap_ms.train"), facts) \
        == pytest.approx(7500 / 1 / 1e6)


def test_idle_under_no_program_span_is_told_apart(facts, capsys):
    trace = dict(facts["trace"])
    trace["host"] = [ev for ev in trace["host"]
                     if not ev[0].startswith("stf/engine/admit")]
    params = metric_params("host_gap_ms.serve")
    value = host_gap_ms.read(params, {"trace": trace})
    logged = json.loads(capsys.readouterr().out)["host_gap"]
    # 3000-4000 now lies under nothing; 6000-7500 (middle 6750) too: the
    # prefill ended at 6600
    assert logged["by_innermost_span_s"]["unlabelled"] == \
        pytest.approx(2500e-9)
    assert logged["under_no_span_share"] == pytest.approx(2500 / 7500)
    assert value == pytest.approx(5000 / 2 / 1e6)


def test_a_trace_without_program_spans_gives_nothing(parent_facts, capsys):
    for name in ("host_gap_ms.serve", "host_gap_ms.train"):
        assert host_gap_ms.read(metric_params(name), parent_facts) is None
    assert capsys.readouterr().out == ""
