"""The reduction from a trace to busy/idle, kernel time and labelled gaps,
on a small hand-built trace (data/small_trace.json, times in ns)."""

import json
import os

import pytest

from chipbench import trace_reduce as tr
from chipbench.readers import idle, mfu, span_minus_busy, trace_events

HERE = os.path.dirname(os.path.abspath(__file__))
FLASH = (r'^(?=.*custom_call_target="tpu_custom_call")'
         r'(?=.*\bf32\[\d+,\d+,1\])(?=.*\bbf16\[\d+,\d+,\d+\])')


@pytest.fixture(scope="module")
def trace():
    with open(os.path.join(HERE, "data", "small_trace.json")) as f:
        raw = json.load(f)
    raw["device"] = {k: [tuple(e) for e in v]
                     for k, v in raw["device"].items()}
    raw["host"] = [tuple(e) for e in raw["host"]]
    return raw


def test_busy_is_the_union_inside_the_window(trace):
    red = tr.reduce(trace, chips=1)
    assert red["window"] == (1000, 11000)
    assert red["window_s"] == pytest.approx(10000e-9)
    # A u B = 1500..4000 (2500), K 2000 + 1000, the straddler 10500..11000
    assert red["busy_s"] == pytest.approx((2500 + 2000 + 1000 + 500) / 1e9)
    assert idle.read({}, {"trace": red}) == pytest.approx(40.0)


def test_pattern_seconds_finds_the_kernel_by_signature(trace):
    ops = trace["device"]["/device:TPU:0"]
    secs, n = tr.pattern_seconds(ops, FLASH, (1000, 11000))
    assert (secs, n) == (pytest.approx(3000e-9), 2)
    assert tr.pattern_seconds(ops, "no_such_kernel", (1000, 11000)) == (0, 0)


def test_gaps_are_labelled_by_the_innermost_open_span(trace):
    red = tr.reduce(trace)
    gaps = dict(red["breakdown"]["idle_gaps"])
    # 1000-1500 nothing open; 4000-6000 one gap, middle 5000 -> dispatch
    # (stage ends there, dispatch starts: the narrower wins ties by width,
    # both 1000 wide, first found stays); 8000-9000 wait; 10000-10500 none
    assert gaps["wait"] == pytest.approx(1000e-9)
    assert gaps["unlabelled"] == pytest.approx(1000e-9)
    assert gaps.get("stage", 0) + gaps.get("dispatch", 0) == \
        pytest.approx(2000e-9)
    assert sum(gaps.values()) == pytest.approx(4000e-9)


def test_gaps_take_the_programs_span_before_the_harnesss():
    """Device busy 0-1000, 2000-3000, 4000-5000, 6000-7000, 8000-9000 of a
    window 0-10000: five gaps of 1000 with middles 1500 ... 9500."""
    ops = [("%fusion.1 = f32[8] fusion()", s, 1000, "")
           for s in range(0, 10000, 2000)]
    host = [
        ("chipbench:window", 0, 10000, "main"),
        ("chipbench:wait_request", 0, 10000, "main"),
        # 1500: a program span on another thread, its metadata cut off,
        # and the narrower of two that are open
        ("stf/engine/step", 1000, 2500, "engine"),
        ("stf/engine/admit#joined=1,held_back=0#", 1200, 700, "engine"),
        # 3500: only the wide one is still open
        # 5500: the harness's narrower span does not beat the program's
        ("stf/session/run", 5000, 1000, "engine"),
        ("chipbench:probe", 5400, 200, "main"),
        # 7500: no program span: the harness's innermost
        ("chipbench:probe", 7400, 200, "main"),
        # 9500: only the harness's window-long wait
        ("PjitFunction(step)", 9400, 200, "engine"),
    ]
    gaps = dict(tr.idle_gaps(ops, host, (0, 10000)))
    assert gaps == {"stf/engine/admit": pytest.approx(1000e-9),
                    "stf/engine/step": pytest.approx(1000e-9),
                    "stf/session/run": pytest.approx(1000e-9),
                    "probe": pytest.approx(1000e-9),
                    "wait_request": pytest.approx(1000e-9)}
    # with no span of either kind a gap stays unlabelled
    bare = dict(tr.idle_gaps(ops, host[:1], (0, 10000)))
    assert bare == {"unlabelled": pytest.approx(5000e-9)}
    assert tr.idle_gaps(ops, host, (0, 10000), n=2)[0][1] == \
        pytest.approx(1000e-9)


def test_labels_fold_instances_and_keep_signatures(trace):
    name = trace["device"]["/device:TPU:0"][3][0]
    assert tr.op_label(name) == \
        "jvp__:custom-call (bf16[4,16,64], f32[4,16,1])"
    assert tr.op_label(name, operands=True, limit=400).endswith(
        "<- bf16[4,16,64], bf16[4,16,64]")
    assert tr.op_label("fusion.12") == "fusion"
    top = tr.reduce(trace)["breakdown"]["device_ops"]
    assert top[0] == ["fusion:fusion bf16[8,128]", pytest.approx(3000e-9)]


def test_readers_return_nothing_when_there_is_nothing_to_read(trace):
    red = tr.reduce(trace)
    peak = {"flops_per_s_bf16": 1e12, "hbm_bytes_per_s": 1e11}
    facts = {"trace": red, "peak": peak, "chips": 1, "work": {}}
    assert trace_events.read({"pattern": FLASH, "work": "flash"}, facts) \
        is None
    assert mfu.read({}, facts) is None
    assert span_minus_busy.read({"span": "session_run"}, facts) is None
    facts["work"] = {"flash": [1.5e3, 10.0], "model_flops": 5e3}
    # least time 1.5e3/1e12 = 1.5 ns against 3000 ns of kernel time
    assert trace_events.read({"pattern": FLASH, "work": "flash"}, facts) \
        == pytest.approx(100 * 1.5 / 3000)
    assert trace_events.read({"pattern": "nothing", "work": "flash"},
                             facts) is None
    assert mfu.read({}, facts) == pytest.approx(100 * 5e3 / 10000e-9 / 1e12)
    facts["spans_in_trace"] = {"session_run": [(0.0, 0.002), (0.002, 0.004)]}
    assert span_minus_busy.read({"span": "session_run"}, facts) == \
        pytest.approx(1000 * 0.002 * 0.4)
