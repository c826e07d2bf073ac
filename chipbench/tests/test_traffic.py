"""The request generator against the committed traffic files, and the
window that offers its requests: pure Python, no device (the runner's
module imports numpy and the harness, nothing of JAX until it is run)."""

import collections
import concurrent.futures
import json
import os
import time

import pytest

from chipbench import harness, traffic
from chipbench.runners import serve

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SEEDS = (0, 7, 3_000_000_021, 2**31 + 5)


def load(*parts):
    with open(os.path.join(BENCH, *parts)) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def manifest():
    with open(os.path.join(os.path.dirname(BENCH), "BENCHMARK.json")) as f:
        return json.load(f)


def _serving_cells(manifest):
    for cell in manifest["workloads"]:
        mix = load("traffic", cell["traffic"] + ".json")
        if mix["kind"] == "requests":
            yield cell, mix, load("configs", cell["config"] + ".json")


def test_the_backlog_cannot_run_dry(manifest):
    """3296 requests in 206 blocks of 16 at the manifest's window: enough
    for 80 finished requests a second, four times what the cell finishes
    (PERF.md section 4), and every one fits the engine's queue."""
    mix = load("traffic", "backlog.json")
    seconds = manifest["run_seconds"]
    assert seconds == 40
    assert traffic.request_count(mix, seconds) == 3296 == 206 * mix["block"]
    assert len(traffic.requests(mix, 32768, SEEDS[0], seconds)) == 3296


def test_every_backlog_fits_its_configurations_queue(manifest):
    cells = list(_serving_cells(manifest))
    assert cells
    for cell, mix, config in cells:
        offered = traffic.request_count(mix, manifest["run_seconds"])
        assert offered <= config["program"]["max_queue_depth"], cell["name"]
        assert offered % mix["block"] == 0


@pytest.mark.parametrize("name", ["backlog", "longdoc-backlog"])
def test_every_seed_holds_the_same_sizes_and_the_same_head(name):
    mix = load("traffic", name + ".json")
    block, seconds = mix["block"], 3.0
    sizes, heads, orders, first_ids = [], [], [], []
    for seed in SEEDS:
        reqs = traffic.requests(mix, 32768, seed, seconds)
        pairs = [(len(r["prompt"]), r["max_new_tokens"]) for r in reqs]
        for b in range(0, len(pairs), block):  # block by block, not only
            sizes.append(collections.Counter(pairs[b:b + block]))  # in all
        heads.append(pairs[0])
        orders.append(pairs)
        first_ids.append(tuple(reqs[0]["prompt"][:8]))
        assert all(r["due"] == 0.0 for r in reqs)
        assert min(int(r["prompt"].min()) for r in reqs) >= 2  # 0 pads, 1 ends
    assert all(s == sizes[0] for s in sizes)
    assert sum(sizes[0].values()) == block
    assert len(set(heads)) == 1
    by_prompt = sorted(sizes[0])
    assert heads[0][0] == by_prompt[block // 2][0]  # the middle pair
    assert len({tuple(o) for o in orders}) == len(SEEDS)  # another order
    assert len(set(first_ids)) == len(SEEDS)  # other token ids
    again = traffic.requests(mix, 32768, SEEDS[-1], seconds)
    assert tuple(again[0]["prompt"][:8]) == first_ids[-1]  # the same seed


def test_the_committed_backlog_is_offered_in_one_order(manifest):
    """What ``lm-big.backlog`` offers: on every seed the same (prompt,
    answer) sizes at the same places of the queue, other token ids."""
    mix = load("traffic", "backlog.json")

    def offered(seed):
        made = [serve._Request(r) for r in traffic.requests(
            mix, 32768, seed, manifest["run_seconds"])]
        return made, serve.in_one_order(made, mix)

    (made_a, a), (made_b, b) = offered(SEEDS[2]), offered(SEEDS[3])
    sizes = [(len(r.prompt), r.budget) for r in a]
    assert len(sizes) == 3296
    assert sizes == [(len(r.prompt), r.budget) for r in b]
    assert [(len(r.prompt), r.budget) for r in made_a] != \
        [(len(r.prompt), r.budget) for r in made_b]
    assert sorted(map(id, a)) == sorted(map(id, made_a))
    assert sizes[0] == sorted(sizes[:16])[8] == (1052, 160)
    assert sizes[:16] != sizes[16:32]
    assert any((x.prompt[:8] != y.prompt[:8]).any() for x, y in zip(a, b))


# -- the window ---------------------------------------------------------------

class _StubServer:
    """Takes every request and never answers: the window's own behaviour
    is what is looked at."""

    def __init__(self):
        self.calls = []

    def generate(self, prompt, model, max_new_tokens, timeout_ms, on_token):
        self.calls.append((len(prompt), model, max_new_tokens, timeout_ms))
        return concurrent.futures.Future()


class _StubTracer(harness.Tracer):
    """``harness.Tracer`` without the profiler: the same arm / poll / stop
    and the same times."""

    def _start(self):
        self.t0 = time.perf_counter()
        self._window = object()

    def stop(self):
        if self._window is not None:
            self.t1 = time.perf_counter()
            self._window = None


def _requests(n=5):
    mix = dict(load("traffic", "backlog.json"), drain_seconds=0.5)
    specs = traffic.requests(mix, 64, 11, 0.1)[:n]
    return mix, [serve._Request(s) for s in specs]


@pytest.fixture
def sleeps(monkeypatch):
    calls = []
    real = time.sleep

    def counted(seconds):
        calls.append(seconds)
        real(seconds)

    monkeypatch.setattr(serve.time, "sleep", counted)
    return calls


def test_the_window_sleeps_once_until_its_close(sleeps):
    mix, reqs = _requests()
    server, opened = _StubServer(), []
    win = serve.drive(server, "m", mix, reqs, 0.3,
                      on_open=lambda: opened.append(time.perf_counter()))
    returned = time.perf_counter()
    assert len(sleeps) == 1  # slept, not polled
    assert abs(returned - win["t_close"]) < 0.02
    assert win["t_close"] - win["t_open"] == pytest.approx(0.3)
    assert win["deadline"] == pytest.approx(win["t_close"] + 0.5)
    assert len(server.calls) == len(reqs) == len(win["lateness"])
    assert opened and win["t_open"] <= opened[0] <= reqs[0].submitted
    assert all(0 <= late < 0.1 for late in win["lateness"])
    # a request may wait until the harness's deadline, not longer
    assert all(0 < c[3] <= 1000 * 0.8 + 1 for c in server.calls)
    assert win["gc_pauses"] == [] or all(
        len(p) == 3 for p in win["gc_pauses"])


@pytest.mark.parametrize("start_after, seconds, t0, t1", [
    (0.10, 0.10, 0.10, 0.20),   # a stretch inside the window
    (0.00, 0.10, 0.00, 0.10),   # from the opening: arm() starts it
    (0.20, 0.50, 0.20, 0.30),   # cut by the close
    (0.50, 0.10, None, None),   # due after the close: never started
])
def test_a_traced_window_wakes_for_the_trace_and_the_close(
        sleeps, start_after, seconds, t0, t1):
    mix, reqs = _requests()
    tracer = _StubTracer(True, seconds, start_after)
    win = serve.drive(_StubServer(), "m", mix, reqs, 0.3, tracer)
    returned = time.perf_counter()
    assert 1 <= len(sleeps) <= 3
    assert abs(returned - win["t_close"]) < 0.02
    if t0 is None:
        assert tracer.t0 is None and tracer.t1 is None
    else:
        assert tracer.t0 - win["t_open"] == pytest.approx(t0, abs=0.02)
        assert tracer.t1 - win["t_open"] == pytest.approx(t1, abs=0.02)


def test_a_tracer_that_is_off_costs_no_wake_up(sleeps):
    mix, reqs = _requests()
    tracer = _StubTracer(False, 0.1, 0.1)
    serve.drive(_StubServer(), "m", mix, reqs, 0.2, tracer)
    assert len(sleeps) == 1 and tracer.t0 is None


# -- what a run logs of its window ----------------------------------------------

def _delivered(steps, live=4):
    """``live`` answers that each get a token at every step's time."""
    mix, reqs = _requests(live)
    for k, r in enumerate(reqs):
        r.times = [t + 1e-5 * k for t in steps]
    return reqs


def test_window_log_finds_the_long_gaps_and_the_pauses():
    steps = [0.010 * i for i in range(1, 21)]          # 20 steps, 10 ms
    steps += [steps[-1] + 0.050]                       # one gap of 50 ms
    steps += [steps[-1] + 0.010 * i for i in range(1, 6)]
    steps += [steps[-1] + 0.020]                       # and one of 20 ms
    reqs = _delivered(steps)
    win = {"t_open": 0.0, "t_close": 1.0,
           "gc_pauses": [(0.100, 0.0004, 0), (0.230, 0.012, 2),
                         (0.300, 0.0006, 0), (1.500, 0.5, 2)]}
    log = serve.window_log(reqs, win)
    gaps = log["decode_gaps_ms"]
    assert gaps["deliveries"] == len(steps)  # 4 tokens a step are one
    assert gaps["median"] == pytest.approx(10.0)
    assert gaps["longest"] == pytest.approx(50.0)
    assert gaps["over_1.5x_median"] == 2
    assert gaps["longest_over_1.5x"] == [[0.2, 50.0], [0.3, 20.0]]
    collector = log["collector"]
    assert collector["collections"] == {"0": 2, "2": 1}  # not the late one
    assert collector["paused_ms"] == pytest.approx(13.0)
    assert collector["longest_ms"] == pytest.approx(12.0)
    assert collector["over_5ms"] == [[0.23, 12.0, 2]]
    assert collector["enabled"] is True


def test_window_log_with_nothing_delivered():
    mix, reqs = _requests(2)
    log = serve.window_log(reqs, {"t_open": 0.0, "t_close": 1.0,
                                  "gc_pauses": []})
    assert log["decode_gaps_ms"] is None
    assert log["collector"]["collections"] == {}
    assert log["collector"]["longest_ms"] == 0.0


def test_pauses_are_recorded_only_while_installed():
    import gc

    with serve._Pauses() as pauses:
        gc.collect()
    assert len(pauses.records) == 1
    start, seconds, generation = pauses.records[0]
    assert generation == 2 and seconds > 0
    gc.collect()
    assert len(pauses.records) == 1 and pauses not in gc.callbacks
