"""The one span primitive (platform/monitoring.traceme) on the profiler's
clock: a short jax.profiler session on the CPU is read back with
jax.profiler.ProfileData and has to hold the Session's and the engine's
``stf/...`` spans, nested as the layers are; without a listener the
primitive records nothing while the telemetry ring keeps the serving
spans; and every Pallas kernel carries a stable name."""

import ast
import glob
import os
import time

import numpy as np
import pytest

import jax

import simple_tensorflow_tpu as stf
from simple_tensorflow_tpu import serving, telemetry
from simple_tensorflow_tpu.platform import monitoring


def _profile(tmp_path, body):
    """Run ``body`` under a profiler session; {thread line: [(name, start,
    end, stats)]} of the ``stf/...`` host events, per line in start
    order."""
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.host_tracer_level = 2
    jax.profiler.start_trace(str(tmp_path), profiler_options=options)
    try:
        body()
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(os.path.join(
        str(tmp_path), "plugins", "profile", "*", "*.xplane.pb"))
    data = jax.profiler.ProfileData.from_file(path)
    lines = {}
    for plane in data.planes:
        if not plane.name.startswith("/host:"):
            continue
        for i, ln in enumerate(plane.lines):
            events = sorted(
                (int(ev.start_ns), int(ev.start_ns + ev.duration_ns),
                 ev.name.split("#")[0], dict(ev.stats))
                for ev in ln.events if ev.name.startswith("stf/"))
            if events:
                lines[(plane.name, i)] = [
                    (name, s, e, stats) for s, e, name, stats in events]
    return lines


def _children(events, parent):
    """Names of the events lying inside ``parent`` = (name, start, end)."""
    _, lo, hi, _ = parent
    return [ev[0] for ev in events
            if ev is not parent and ev[1] >= lo and ev[2] <= hi]


class _FakeModel:
    """Duck-typed slot model: every sequence emits token 7 twice, then
    EOS."""

    eos_id, pad_id, src_len, num_slots, max_decode_len = 1, 0, 4, 4, 8

    def __init__(self):
        self.steps = {}

    def prefill(self, src_rows, slots):
        for slot in np.asarray(slots):
            self.steps[int(slot)] = 0

    def decode(self, tokens, positions, slots):
        time.sleep(0.002)
        out = []
        for slot in np.asarray(slots):
            self.steps[int(slot)] += 1
            out.append(7 if self.steps[int(slot)] <= 2 else self.eos_id)
        return (np.asarray(out, np.int32),
                np.full(len(out), -0.5, np.float32), len(out))

    def close(self):
        pass


def _toy_session():
    x = stf.placeholder(stf.float32, [4, 3], name="x")
    w = stf.Variable(np.ones((3, 2), np.float32), name="w")
    y = stf.matmul(x, w)
    with stf.control_dependencies([y]):
        step = stf.assign_add(w, stf.ones([3, 2]))
    sess = stf.Session()
    sess.run(stf.global_variables_initializer())
    feed = {x: np.ones((4, 3), np.float32)}
    sess.run([y, step], feed)  # planned and compiled before the trace
    return sess, [y, step], feed


def test_session_run_spans_nest_in_the_profile(tmp_path):
    with stf.Graph().as_default():
        sess, fetches, feed = _toy_session()
        lines = _profile(tmp_path, lambda: [sess.run(fetches, feed)
                                            for _ in range(2)])
        sess.close()
    (events,) = lines.values()
    runs = [ev for ev in events if ev[0] == "stf/session/run"]
    assert len(runs) == 2
    for run in runs:
        inside = _children(events, run)
        assert inside == ["stf/session/" + phase for phase in (
            "prepare", "stage_feeds", "device_execute", "commit",
            "assemble", "fetch", "await_device", "copy_to_host",
            "assemble")]
    # the commit is the device stage's child, the wait and the copy the
    # fetch's, in that order; meta arrives as stats
    execs = [ev for ev in events if ev[0] == "stf/session/device_execute"]
    assert _children(events, execs[0]) == ["stf/session/commit"]
    for fetch in (ev for ev in events if ev[0] == "stf/session/fetch"):
        assert _children(events, fetch) == ["stf/session/await_device",
                                            "stf/session/copy_to_host"]
    for name in ("prepare", "assemble", "await_device", "copy_to_host"):
        for ev in events:
            if ev[0] == "stf/session/" + name:
                assert _children(events, ev) == []
    stage = next(ev for ev in events if ev[0] == "stf/session/stage_feeds")
    assert stage[3].get("n_feeds") == 1
    # nothing was planned or compiled inside the trace: a recompile would
    # show as a jit_compile / prune span at its step
    assert not any(ev[0].endswith(("/prune", "/jit_compile"))
                   for ev in events)


def _await_device_cell():
    return monitoring.get_metric(
        "/stf/session/await_device_seconds").get_cell()


def test_the_wait_on_the_device_is_sampled_where_its_span_is(tmp_path):
    """One sample of ``/stf/session/await_device_seconds`` a run that
    fetches a device value, from two clock reads inside the span; none
    for a run that fetches nothing, whose fetch holds neither child."""
    with stf.Graph().as_default():
        sess, (y, step), feed = _toy_session()
        plan = sess.plan({"y": y}, feeds=list(feed))
        plan.execute(feed)
        before = _await_device_cell().value()
        lines = _profile(tmp_path, lambda: (sess.run(y, feed),
                                            sess.run(step.op, feed),
                                            plan.execute(feed)))
        after = _await_device_cell().value()
        sess.close()
    (events,) = lines.values()
    waits = [ev for ev in events if ev[0] == "stf/session/await_device"]
    fetches = [ev for ev in events if ev[0] == "stf/session/fetch"]
    assert len(fetches) == 3 and len(waits) == 2
    assert after["count"] - before["count"] == 2
    assert [_children(events, f) for f in fetches] == [
        ["stf/session/await_device", "stf/session/copy_to_host"], [],
        ["stf/session/await_device", "stf/session/copy_to_host"]]
    sampled_ns = (after["sum"] - before["sum"]) * 1e9
    spanned_ns = sum(ev[2] - ev[1] for ev in waits)
    assert 0 <= sampled_ns <= spanned_ns
    # an ExecutionPlan's run closes with the rebuild, inside session/run
    last = [ev for ev in events if ev[0] == "stf/session/run"][-1]
    assert _children(events, last)[-1] == "stf/session/assemble"


def test_a_run_with_async_fetches_waits_for_nothing(tmp_path):
    """Lazy fetches stay lazy: no wait is added, no ``await_device`` span
    is opened and nothing is sampled until the future is asked."""
    with stf.Graph().as_default():
        sess, (y, _), feed = _toy_session()
        plan = sess.plan(y, feeds=list(feed))
        plan.execute(feed)
        before = _await_device_cell().value()["count"]
        got = []
        lines = _profile(tmp_path, lambda: got.append(
            plan.execute(feed, as_futures=True)))
        assert _await_device_cell().value()["count"] == before
        assert isinstance(got[0], stf.FetchFuture)
        np.testing.assert_array_equal(np.asarray(got[0]),
                                      plan.execute(feed))
        sess.close()
    (events,) = lines.values()
    names = [ev[0] for ev in events]
    assert "stf/session/fetch" in names
    assert "stf/session/await_device" not in names
    assert "stf/session/copy_to_host" not in names


def test_a_new_plan_inside_the_trace_shows_its_planning_spans(tmp_path):
    with stf.Graph().as_default():
        sess, fetches, feed = _toy_session()
        z = fetches[0] * 2.0
        lines = _profile(tmp_path, lambda: sess.run(z, feed))
        sess.close()
    (events,) = lines.values()
    (run,) = [ev for ev in events if ev[0] == "stf/session/run"]
    inside = _children(events, run)
    for phase in ("prune", "optimize", "lower", "device_execute", "fetch"):
        assert "stf/session/" + phase in inside, (phase, inside)


def test_engine_spans_nest_on_the_engines_thread(tmp_path):
    step_seconds = monitoring.get_metric(
        "/stf/serving/decode_step_seconds").get_cell("span_plane")
    sampled = step_seconds.value()

    def body():
        with monitoring.traceme("test/caller"):
            pol = serving.DecodePolicy(num_slots=4, max_decode_len=8,
                                       max_new_tokens=6)
            with serving.GenerativeEngine("span_plane", _FakeModel(),
                                          pol) as eng:
                futs = [eng.generate(np.array([i, 0, 0, 0], np.int32))
                        for i in range(3)]
                for f in futs:
                    assert f.result(30)["outcome"] == "eos"

    lines = _profile(tmp_path, body)
    mine = [evs for evs in lines.values()
            if any(ev[0] == "stf/test/caller" for ev in evs)]
    engine = [evs for evs in lines.values()
              if any(ev[0] == "stf/engine/step" for ev in evs)]
    assert len(mine) == 1 and len(engine) == 1
    # the engine thread's spans are on that thread's line, not the caller's
    assert not any(ev[0].startswith("stf/engine/") for ev in mine[0])
    events = engine[0]
    admits = [ev for ev in events if ev[0] == "stf/engine/admit"]
    assert admits and sum(ev[3]["joined"] for ev in admits) == 3
    for admit in admits:
        assert _children(events, admit) == ["stf/engine/prefill"]
        assert admit[3]["held_back"] == 0
    steps = [ev for ev in events if ev[0] == "stf/engine/step"]
    assert len(steps) >= 3
    for step in steps:
        assert _children(events, step) == ["stf/engine/decode",
                                           "stf/engine/deliver"]
    decodes = [ev for ev in events if ev[0] == "stf/engine/decode"]
    assert decodes[0][3]["live"] >= 1 and decodes[0][3]["bucket"] >= 1
    # the span and /stf/serving/decode_step_seconds time the same interval
    after = step_seconds.value()
    assert after["count"] - sampled["count"] == len(decodes)
    spanned_s = sum(ev[2] - ev[1] for ev in decodes) / 1e9
    assert spanned_s == pytest.approx(after["sum"] - sampled["sum"],
                                      rel=0.1)
    prefill = next(ev for ev in events if ev[0] == "stf/engine/prefill")
    assert (prefill[3]["calls"], prefill[3]["rows"]) == (1, 1)
    # the queue ran empty between the first request and its joiners, or
    # at the end: the wait is a span of its own, never a parent of work
    for wait in (ev for ev in events if ev[0] == "stf/engine/wait"):
        assert _children(events, wait) == []


def test_a_paged_step_nests_the_model_and_the_session(tmp_path):
    """The engine's thread, a paged causal LM: ``engine/step`` holds the
    page faults, the decode call and the delivery; ``engine/decode`` the
    model's feed building, its one ``session/run`` and what it does with
    the results; ``engine/prefill`` the model's row building and its
    call."""
    from simple_tensorflow_tpu.models import causal_lm, transformer

    model = causal_lm.CausalLMGenerativeModel(
        transformer.TransformerConfig.tiny(), page_len=4, pages_per_seq=4,
        num_pages=12, max_live=3, prefill_bucket_sizes=(1, 2),
        aot_warmup=False, init_fresh=True, seed=11,
        metrics_label="span_plane_paged")
    pol = serving.DecodePolicy(num_slots=3, max_decode_len=model.max_seq_len,
                               bucket_sizes=[1, 3], max_new_tokens=4)
    prompt = np.arange(2, 8, dtype=np.int32)
    with serving.GenerativeEngine("span_plane_paged", model, pol) as eng:
        eng.generate(prompt).result(120)    # every program compiled
        lines = _profile(
            tmp_path, lambda: eng.generate(prompt + 1).result(120))
    (events,) = [evs for evs in lines.values()
                 if any(ev[0] == "stf/engine/step" for ev in evs)]
    steps = [ev for ev in events if ev[0] == "stf/engine/step"]
    assert 2 <= len(steps) <= 4     # an end token may cut the answer short
    for step in steps:
        direct = [name for name in _children(events, step)
                  if name.startswith("stf/engine/")]
        assert direct == ["stf/engine/page_faults", "stf/engine/decode",
                          "stf/engine/deliver"]
    run = ["stf/session/" + phase for phase in (
        "run", "prepare", "stage_feeds", "device_execute", "commit",
        "assemble", "fetch", "await_device", "copy_to_host", "assemble")]
    for decode in (ev for ev in events if ev[0] == "stf/engine/decode"):
        assert _children(events, decode) == [
            "stf/model/decode_feeds", *run, "stf/model/after_decode"]
    # a page fault that copies-on-write runs the model's copy_page: the
    # span is the parent of that session/run, the decode span's is not in it
    faults = [ev for ev in events if ev[0] == "stf/engine/page_faults"]
    assert not any("stf/model/decode_feeds" in _children(events, ev)
                   for ev in faults)
    # the prompt's page chunks: the row building, then per program call
    # the padding to its bucket and the call, which fetches nothing
    (prefill,) = [ev for ev in events if ev[0] == "stf/engine/prefill"]
    inside = _children(events, prefill)
    assert inside[:3] == ["stf/model/prefill_feeds",
                          "stf/model/prefill_feeds", "stf/session/run"]
    assert "stf/session/await_device" not in inside
    model.close()


def test_without_a_listener_the_primitive_records_nothing():
    assert not monitoring.tracing_active()
    assert not jax.profiler.TraceAnnotation.is_enabled()
    with monitoring.traceme("session/nothing", k=1) as sp:
        sp.set_meta(j=2)
        assert sp._ann is None and sp._sinks is None
    assert sp._ann is None and sp.meta == {"k": 1, "j": 2}
    # the same holds of every span a run opens: a collection on ANOTHER
    # thread hears nothing of this thread's run
    import threading

    with stf.Graph().as_default():
        sess, fetches, feed = _toy_session()
        with monitoring.trace_collection() as elsewhere:
            worker = threading.Thread(
                target=lambda: sess.run(fetches, feed), name="stf_test_run")
            worker.start()
            worker.join()
        assert elsewhere.drain() == []
        # and on this thread it hears the new phases by their names
        with monitoring.trace_collection() as here:
            sess.run(fetches, feed)
        sess.close()
    assert [span["name"] for span in here.drain()] == [
        "prepare", "stage_feeds", "commit", "device_execute", "assemble",
        "await_device", "copy_to_host", "fetch", "assemble", "run"]
    # a collection hears the phase name; the profiler would hear
    # stf/session/heard
    with monitoring.trace_collection() as buf:
        with monitoring.traceme("session/heard", k=1) as sp:
            sp.set_meta(j=2)
    (span,) = buf.drain()
    assert span["name"] == "heard"
    assert span["meta"] == {"k": 1, "j": 2}


def test_the_ring_keeps_the_serving_spans_with_no_profiler():
    telemetry.clear_spans()
    queue_wait = monitoring.get_metric("/stf/serving/queue_wait_seconds")
    pol = serving.DecodePolicy(num_slots=4, max_decode_len=8,
                               max_new_tokens=6)
    with serving.GenerativeEngine("span_ring", _FakeModel(), pol) as eng:
        before = queue_wait.get_cell("span_ring").value()["count"]
        fut = eng.generate(np.array([5, 0, 0, 0], np.int32),
                           trace_id="feedfacefeedface")
        fut.result(30)
        assert queue_wait.get_cell("span_ring").value()["count"] == \
            before + 1
    mine = telemetry.recent_spans(trace_id="feedfacefeedface")
    assert [s["name"] for s in mine] == ["serving_queue_wait",
                                         "serving_decode_prefill"]
    prefill = mine[1]
    assert prefill["meta"] == {"model": "span_ring", "joined": 1,
                               "calls": 1, "rows": 1}
    assert prefill["thread"].startswith("stf_serving_decode_")
    assert prefill["dur_s"] >= 0
    # the engine's step spans are the primitive alone: none reach the ring
    assert not any(s["name"].startswith(("engine", "stf/"))
                   for s in telemetry.recent_spans())


def test_a_ring_span_is_the_primitive_plus_one_entry():
    telemetry.clear_spans()
    with monitoring.trace_collection() as buf:
        with telemetry.trace_scope("0123456789abcdef"):
            with telemetry.span("serving/probe", ring="serving_probe",
                                detail="x") as sp:
                pass
    (heard,) = buf.drain()
    assert heard["name"] == "probe" and heard["meta"] == {"detail": "x"}
    (kept,) = telemetry.recent_spans()
    assert kept["name"] == "serving_probe"
    assert kept["trace_id"] == "0123456789abcdef"
    assert kept["dur_s"] == sp.dur_s and kept["start_s"] == sp.start_s
    assert isinstance(sp, monitoring.traceme)


# -- every Pallas kernel has a stable name ------------------------------------

_PALLAS_DIR = os.path.join(os.path.dirname(os.path.abspath(stf.__file__)),
                           "ops", "pallas")


def _pallas_call_sites():
    sites = []
    for path in sorted(glob.glob(os.path.join(_PALLAS_DIR, "*.py"))):
        with open(path) as f:
            tree = ast.parse(f.read())
        for node in ast.walk(tree):
            if (isinstance(node, ast.Call)
                    and isinstance(node.func, ast.Attribute)
                    and node.func.attr == "pallas_call"):
                sites.append(pytest.param(
                    node, id=f"{os.path.basename(path)}:{node.lineno}"))
    return sites


_SITES = _pallas_call_sites()


def test_the_scan_finds_the_kernels():
    assert len(_SITES) >= 12


@pytest.mark.parametrize("call", _SITES)
def test_every_pallas_call_has_a_stable_name(call):
    (name,) = [kw.value for kw in call.keywords if kw.arg == "name"]
    if isinstance(name, ast.JoinedStr):  # f"stf_decode_attention_q{kq}"
        head = name.values[0]
        assert isinstance(head, ast.Constant)
        text = head.value
    else:
        assert isinstance(name, ast.Constant)
        text = name.value
    assert text.startswith("stf_") and text == text.lower()
    assert " " not in text and "-" not in text


def test_kernel_names_are_distinct():
    names = [ast.unparse(kw.value) for site in _SITES
             for kw in site.values[0].keywords if kw.arg == "name"]
    assert len(names) == len(set(names)) == len(_SITES)
