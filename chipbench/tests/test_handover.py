"""The reader that splits an idle gap where the program's spans split it
(``readers/idle_split_ms``), on a hand-built trace (data/handover_trace.json;
the tables below are in us): every piece of a 4 ms gap that crosses a dozen
spans, the two directions of the executor's wait, a gap under no span, a run
on another thread, the parts against the whole, a device clock that leads or
lags the host's put right from the runtime's own marks, and nothing to read
-> None, never 0."""

import json
import os

import pytest

from chipbench import trace_reduce as tr
from chipbench.readers import (host_gap_ms, idle_split_ms, process_counter,
                               span_ms)

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
S, E, M = "stf/session/", "stf/engine/", "stf/model/"
US = 1000  # the tables are in us, the trace in ns

# what the second gap, 6000..10000 us, is made of: (label, from, to)
SECOND_GAP = [
    ("return", 6000, 6400),          # step A's wait, opened at 1900
    (S + "copy_to_host", 6400, 6600),
    (S + "fetch", 6600, 6650),
    (S + "assemble", 6650, 6700),
    (S + "run", 6700, 6900),
    (E + "decode", 6900, 6920),
    (M + "after_decode", 6920, 6990),
    (E + "decode", 6990, 7000),
    (E + "step", 7000, 7050),
    (E + "deliver", 7050, 7500),
    (E + "step", 7500, 7600),
    ("unlabelled", 7600, 7700),      # between two engine steps
    (E + "step", 7700, 7720),
    (E + "page_faults", 7720, 7900),
    (E + "step", 7900, 7950),
    (E + "decode", 7950, 8000),
    (M + "decode_feeds", 8000, 8500),
    (E + "decode", 8500, 8550),
    (S + "run", 8550, 8600),
    (S + "prepare", 8600, 8700),
    (S + "stage_feeds", 8700, 8800),
    (S + "device_execute", 8800, 9300),
    (S + "commit", 9300, 9450),
    (S + "device_execute", 9450, 9500),
    (S + "assemble", 9500, 9550),
    (S + "fetch", 9550, 9600),
    ("launch", 9600, 10000),         # step B's wait, opened in the gap
]

# all four gaps (1000-2000, the one above, 20000-24000, 26000-28000)
BY_SPAN = {
    "return": 400 + 300 + 500, "launch": 100 + 400 + 1500,
    S + "copy_to_host": 200 + 100 + 100,
    S + "device_execute": 800 + 550 + 300, S + "commit": 150,
    S + "run": 50 + 250 + 60 + 200 + 400, S + "fetch": 50 + 100 + 20,
    S + "assemble": 100 + 20, S + "prepare": 100, S + "stage_feeds": 100,
    E + "decode": 130 + 40, M + "after_decode": 70 + 60,
    M + "decode_feeds": 500, E + "step": 220 + 150,
    E + "deliver": 450 + 350, E + "page_faults": 180,
    E + "wait": 900 + 500, "unlabelled": 100 + 500}
RETURN = BY_SPAN["return"] + BY_SPAN[S + "copy_to_host"]
LAUNCH = (BY_SPAN["launch"] + BY_SPAN[S + "device_execute"]
          + BY_SPAN[S + "commit"])
IDLE = 1000 + 4000 + 4000 + 2000


def metric_params(name):
    with open(os.path.join(BENCH, "layer_metrics", name + ".json")) as f:
        return json.load(f)["params"]


def _facts(name, edit=None):
    with open(os.path.join(HERE, "data", name)) as f:
        raw = json.load(f)
    raw["device"] = {k: [tuple(e) for e in v]
                     for k, v in raw["device"].items()}
    raw["host"] = [tuple(e) for e in raw["host"]]
    if edit:
        raw["host"] = edit(raw["host"])
    return {"trace": tr.reduce(raw, chips=1)}


@pytest.fixture(scope="module")
def facts():
    return _facts("handover_trace.json")


@pytest.fixture(scope="module")
def parent_facts():
    """A trace of a program that has the older spans and not the split:
    what the parent commit gives the new metrics."""
    return _facts("span_trace.json")


def _logged(capsys):
    return json.loads(capsys.readouterr().out)["idle_split"]


def test_the_tables_above_agree():
    assert sum(b - a for _, a, b in SECOND_GAP) == 4000
    assert all(b == a for (_, _, b), (_, a, _)
               in zip(SECOND_GAP, SECOND_GAP[1:]))
    assert sum(BY_SPAN.values()) == IDLE


def test_a_gap_is_cut_at_every_span_boundary(facts):
    trace = facts["trace"]
    gaps = idle_split_ms.gaps_of(tr.merged_intervals(trace["ops"]),
                                 trace["window"])
    assert gaps == [(a * US, b * US) for a, b in (
        (1000, 2000), (6000, 10000), (20000, 24000), (26000, 28000))]
    spans = [ev for ev in trace["host"] if ev[0].startswith("stf/")]
    spans = [(name.split("#")[0], s, d, th) for name, s, d, th in spans]
    expected = {}
    for label, a, b in SECOND_GAP:
        expected[label] = expected.get(label, 0) + (b - a) * US
    assert idle_split_ms.split(gaps[1:2], spans) == expected
    # piece by piece: each alone is given to the same span
    for label, a, b in SECOND_GAP:
        if label in ("return", "launch"):
            continue  # told apart by the gap's own start, below
        assert idle_split_ms.split([(a * US, b * US)], spans) == {
            label: (b - a) * US}


def test_every_piece_of_every_gap(facts, capsys):
    params = {"per": "stf/engine/decode", "take": ["return"]}
    assert idle_split_ms.read(params, facts) == \
        pytest.approx(BY_SPAN["return"] / 1 / 1e3)
    logged = _logged(capsys)
    assert logged["by_innermost_span_s"] == {
        k: pytest.approx(v / 1e6) for k, v in BY_SPAN.items()}
    assert logged["idle_s"] == pytest.approx(IDLE / 1e6)
    assert logged["return_s"] == pytest.approx(1200e-6)
    assert logged["launch_s"] == pytest.approx(2000e-6)
    # decode A opened before the window: one whole step
    assert logged["steps"] == 1
    assert logged["under_no_span_share"] == pytest.approx(600 / IDLE)
    assert logged["under_program_spans_ms_per_step"] == \
        pytest.approx((IDLE - 600) / 1e3)
    assert (logged["gaps"], logged["gap_median_ns"],
            logged["gap_longest_ns"]) == (4, 3000 * US, 4000 * US)
    # waits end 400, 300 and 500 after the busy interval before them
    assert logged["min_return_ns"] == 300 * US
    # the runtime heard of every awaited program 100 after its last op and
    # enqueued every next one 100 before its first: the clocks agree
    assert logged["clock_skew_window_ns"] == [-100 * US, 100 * US]
    assert logged["clock_skew_ns"] == 0


@pytest.mark.parametrize("metric, expected", [
    ("handover_return_ms.serve", RETURN / 1),
    ("handover_launch_ms.serve", LAUNCH / 1),
    ("host_serial_ms.serve", (IDLE - 600 - RETURN - LAUNCH) / 1),
    # two whole runs: step B's and the main thread's
    ("handover_return_ms.train", RETURN / 2),
    ("handover_launch_ms.train", LAUNCH / 2),
])
def test_the_metrics_files(facts, parent_facts, metric, expected):
    params = metric_params(metric)
    assert idle_split_ms.read(params, facts) == pytest.approx(expected / 1e3)
    assert idle_split_ms.read(params, parent_facts) is None
    assert idle_split_ms.read(params, {}) is None


def test_the_parts_are_the_whole(facts, capsys):
    parts = [idle_split_ms.read(metric_params(name + ".serve"), facts)
             for name in ("handover_return_ms", "handover_launch_ms",
                          "host_serial_ms")]
    whole = _logged_last(capsys)["under_program_spans_ms_per_step"]
    assert sum(parts) == pytest.approx(whole)
    # host_gap_ms gives whole gaps to the span at their middle: here all
    # four middles lie under a program span, so it reads the unlabelled
    # 600 ns too
    by_middle = host_gap_ms.read(metric_params("host_gap_ms.serve"), facts)
    assert by_middle == pytest.approx(IDLE / 1e3)
    assert sum(parts) == pytest.approx(by_middle - 600 / 1e3)


def _logged_last(capsys):
    lines = [json.loads(ln) for ln in capsys.readouterr().out.splitlines()]
    return [ln["idle_split"] for ln in lines if "idle_split" in ln][-1]


def test_a_gap_under_no_span_is_unlabelled(capsys):
    only_engine = _facts("handover_trace.json", lambda host: [
        ev for ev in host if ev[3] != "main" or ev[0] == "chipbench:window"])
    idle_split_ms.read(metric_params("host_serial_ms.serve"), only_engine)
    by_span = _logged(capsys)["by_innermost_span_s"]
    # the main thread's run is gone: the engine's wait takes 21100..27500
    # and what follows it lies under nothing
    assert by_span["unlabelled"] == pytest.approx((100 + 500) / 1e6)
    assert by_span[E + "wait"] == pytest.approx((2900 + 1500) / 1e6)
    assert by_span["launch"] == pytest.approx((100 + 400) / 1e6)


def _skewed(lead_us, marks=True):
    """The trace as a profiler session would leave it whose device clock
    LEADS the host's by ``lead_us``: every device op that much early."""
    with open(os.path.join(HERE, "data", "handover_trace.json")) as f:
        raw = json.load(f)
    raw["device"] = {k: [(n, s - lead_us * US, d, x) for n, s, d, x in v]
                     for k, v in raw["device"].items()}
    raw["host"] = [tuple(e) for e in raw["host"]
                   if marks or e[3] not in ("futex", "queue")]
    return {"trace": tr.reduce(raw, chips=1)}


@pytest.mark.parametrize("lead_us, recorded_us", [
    # as recorded, the smallest wait's end after its program's: 300 more
    # when the device's clock leads by 300; below zero when it lags by more
    # than the smallest return; and below zero again when it leads by as
    # much as the host takes to turn round (the main thread's next program
    # then seems begun before its wait ended)
    (300, 600), (-700, -400), (1500, -4000)])
def test_a_device_clock_that_leads_or_lags_is_put_right(lead_us, recorded_us,
                                                        capsys):
    """Where the device's clock leads by 1.5 ms every program seems to
    begin before the runtime enqueued it and the host seems to hear of
    its end 1.5 ms late: the runtime's marks bound the lead, the ops are
    moved back by the window's middle, and every piece reads as it does
    on clocks that agree."""
    skewed = _skewed(lead_us)
    for name, expected in (("handover_return_ms", RETURN),
                           ("handover_launch_ms", LAUNCH),
                           ("host_serial_ms", IDLE - 600 - RETURN - LAUNCH)):
        assert idle_split_ms.read(metric_params(name + ".serve"), skewed) \
            == pytest.approx(expected / 1e3)
    logged = _logged_last(capsys)
    assert logged["clock_skew_window_ns"] == [(lead_us - 100) * US,
                                              (lead_us + 100) * US]
    assert logged["clock_skew_ns"] == lead_us * US
    assert logged["by_innermost_span_s"] == {
        k: pytest.approx(v / 1e6) for k, v in BY_SPAN.items()}
    assert logged["min_return_ns"] == recorded_us * US


def test_without_the_runtimes_marks_nothing_is_moved(capsys):
    """No ``DoEnqueueProgram`` / ``Execute=>Done`` in the trace: the window
    cannot be had, the ops stay where the profiler put them and the log
    says so; what the skew does to the pieces is then theirs to carry."""
    value = idle_split_ms.read(metric_params("handover_return_ms.serve"),
                               _skewed(300, marks=False))
    logged = _logged(capsys)
    assert logged["clock_skew_window_ns"] is None
    assert logged["clock_skew_ns"] == 0
    # every gap begins 300 early: each of the three returns reads 300 more
    assert value == pytest.approx((RETURN + 3 * 300) / 1e3)
    assert logged["min_return_ns"] == 600 * US


def test_a_wait_that_ends_before_its_program_shows_below_zero(capsys):
    def early(host):  # step B's wait ends 200 before its program does
        return [(n, s, 19800 * US - s, th)
                if (n, s) == (S + "await_device", 9600 * US)
                else (n, s, d, th) for n, s, d, th in host]

    idle_split_ms.read(metric_params("handover_return_ms.serve"),
                       _facts("handover_trace.json", early))
    assert _logged(capsys)["min_return_ns"] == -200 * US


def test_a_trace_without_the_wait_gives_nothing(facts, parent_facts, capsys):
    no_wait = dict(facts["trace"])
    no_wait["host"] = [ev for ev in no_wait["host"]
                       if not ev[0].startswith(S + "await_device")]
    no_steps = dict(facts["trace"])
    no_steps["host"] = [ev for ev in no_steps["host"]
                        if not ev[0].startswith(E + "decode")]
    for name in ("handover_return_ms", "handover_launch_ms",
                 "host_serial_ms"):
        params = metric_params(name + ".serve")
        assert idle_split_ms.read(params, parent_facts) is None
        assert idle_split_ms.read(params, {"trace": no_wait}) is None
        assert idle_split_ms.read(params, {"trace": no_steps}) is None
    assert capsys.readouterr().out == ""


def test_the_model_layers_host_time(facts, parent_facts, capsys):
    params = metric_params("model_host_ms.serve")
    # decode_feeds 500 + after_decode 60 of step B, 70 of step A's tail
    assert span_ms.read(params, facts) == pytest.approx(630 / 1 / 1e3)
    also = json.loads(capsys.readouterr().out)["span_ms"]["also"]
    assert also == {"decode_feeds_ms": pytest.approx(500 / 1e3),
                    "after_decode_ms": pytest.approx(130 / 1e3),
                    "prefill_feeds_ms": 0}
    assert span_ms.read(params, parent_facts) is None


def test_the_wait_over_the_whole_window():
    params = metric_params("await_device_ms.serve")
    assert params["labels"] == [] and params["stat"] == "mean"
    before = {"count": 10, "sum": 0.5}
    after = {"count": 14, "sum": 0.54}
    name = "/stf/session/await_device_seconds"
    assert process_counter.read(
        params, {"counters": {name: (before, after)}}) == pytest.approx(10.0)
    # the parent has no such sampler: the harness reads None on both sides
    assert process_counter.read(
        params, {"counters": {name: (None, None)}}) is None
    assert process_counter.read(params, {"counters": {}}) is None
