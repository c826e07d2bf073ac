"""The pipelined paged decode step (serving/generative.py ``_step_paged``):
step k + 1 is enqueued before step k is fetched, its tokens taken on the
device from the slot-indexed last-token buffer step k writes
(models/causal_lm.py ``build_paged_lm_program``). Over every paged block
stack at toy widths: the engine delivers token for token the greedy
streams and log-probabilities of serial decoding of the same requests —
an end token seen one step late delivers nothing past it, a sequence at
its budget never rides the next step, joiners are admitted while a step
is in flight, a decode position crosses into a page of its own, a
deadline and a device error retire what they should — and building the
model and serving a burst compiles and plans no more than before."""

import dataclasses
import time

import numpy as np
import pytest

import jax.monitoring

import simple_tensorflow_tpu as stf
from simple_tensorflow_tpu import serving
from simple_tensorflow_tpu.framework import errors
from simple_tensorflow_tpu.platform import monitoring

STACKS = ["causal_lm", "sparse", "latent", "state_space", "hybrid",
          "short_conv"]
PAGE = 8
SLOTS = 4
# prompt lengths and budgets of one batch: six requests for four slots, so
# two join while a step is in flight; a 9-token prompt's first decode
# position opens a page of its own, a 16-token prompt's second one does,
# an 8-token prompt's first append copies its shared tail page on write
PROMPTS = (5, 9, 16, 3, 12, 8)
BUDGETS = (9, 4, 12, 1, 6, 10)


def _model(which, aot_warmup=False, decode=(SLOTS,), prefill=(SLOTS,),
           max_live=SLOTS):
    """The stack ``which`` at toy widths, float32, by default one decode
    and one prefill bucket (XLA's CPU matmuls may part in the last place
    between buckets; within one, a row's numbers do not depend on its
    neighbours)."""
    kw = dict(page_len=PAGE, pages_per_seq=8, num_pages=40,
              max_live=max_live, decode_bucket_sizes=list(decode),
              prefill_bucket_sizes=list(prefill), init_fresh=True, seed=3,
              compute_dtype=stf.float32, aot_warmup=aot_warmup,
              metrics_label=f"pipeline_{which}")
    if which == "causal_lm":
        from simple_tensorflow_tpu.models import causal_lm, transformer

        return causal_lm.CausalLMGenerativeModel(dataclasses.replace(
            transformer.TransformerConfig.tiny(), max_len=64), **kw)
    from chipbench import harness
    from chipbench.tests import (tiny_hybrid_linear_moe, tiny_latent_moe,
                                 tiny_short_conv_moe, tiny_sparse_moe,
                                 tiny_state_space_moe)

    tiny = {"sparse": tiny_sparse_moe, "latent": tiny_latent_moe,
            "state_space": tiny_state_space_moe,
            "hybrid": tiny_hybrid_linear_moe,
            "short_conv": tiny_short_conv_moe}[which]
    prog = tiny.config("float32")["program"]
    cfg = harness.import_attr(prog["config_class"])(**prog["config_kwargs"])
    return harness.import_attr(prog["model_class"])(cfg, **kw)


def _prompts(model, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.integers(2, model.cfg.vocab_size, size=n).astype(np.int32)
            for n in PROMPTS]


def _serial(model, prompt, steps):
    """One request alone, token after token: its prompt's page chunks as
    the rows of one prefill call, then each decode position enqueued and
    fetched with its token given by the host. (tokens, logps)."""
    pps = model.pages_per_seq
    table = np.arange(pps, dtype=np.int32)
    body = prompt[:-1]
    chunks = -(-len(body) // PAGE)
    if chunks:
        tok = np.full((chunks, PAGE), model.pad_id, np.int32)
        tok.reshape(-1)[:len(body)] = body
        model.prefill_chunk(
            tok, PAGE * np.arange(chunks), np.stack([table] * chunks),
            table[:chunks], slots=[0] * chunks,
            lens=[min(PAGE, len(body) - PAGE * c) for c in range(chunks)])
    tok, pos, toks, lps = int(prompt[-1]), len(prompt) - 1, [], []
    for _ in range(steps):
        nxt, lp, _ = model.decode([tok], [pos], table[None], slots=[0])
        toks.append(int(nxt[0]))
        lps.append(float(lp[0]))
        tok, pos = toks[-1], pos + 1
    return toks, lps


def _cut(stream, eos):
    toks, lps = stream
    n = toks.index(eos) + 1 if eos in toks else len(toks)
    return toks[:n], lps[:n]


_SERVED = {}


@pytest.fixture(scope="module", params=STACKS)
def served(request):
    """(model, prompts, serial streams at the longest budget, an end token
    that ends at least one stream part-way). The end token is the first
    token of a stream that another stream emits later on."""
    which = request.param
    if which not in _SERVED:
        model = _model(which)
        prompts = _prompts(model)
        streams = [_serial(model, p, max(BUDGETS)) for p in prompts]
        firsts = {s[0][0] for s in streams}
        late = [t for s in streams for t in s[0][1:max(BUDGETS) - 1]
                if t in firsts and t != s[0][0]]
        eos = late[0] if late else streams[0][0][2]
        _SERVED[which] = (model, prompts, streams, eos)
    yield _SERVED[which]


@pytest.fixture(scope="module", autouse=True)
def _close_models():
    yield
    for model, _, _, _ in _SERVED.values():
        model.close()
    _SERVED.clear()


class _Recording:
    """Wraps a model's ``decode_async`` / ``collect`` / ``prefill_chunk``
    to record the rows of every enqueued step, whether a step was in
    flight when a prefill ran, and to raise at a chosen collect; and keeps
    the engine's close from closing the model, which the next test
    serves again."""

    def __init__(self, model, engine_box, fail_at=None):
        self.model, self.box, self.fail_at = model, engine_box, fail_at
        self.steps = []           # (slots, positions, tokens) a step
        self.prefill_in_flight = []
        self.collects = 0
        self._orig = {}

    def __enter__(self):
        m = self.model
        for name in ("decode_async", "collect", "prefill_chunk", "close"):
            self._orig[name] = getattr(m, name)
        orig = self._orig

        def decode_async(tokens, positions, tables, slots=None):
            self.steps.append((list(slots), list(positions), list(tokens)))
            return orig["decode_async"](tokens, positions, tables, slots)

        def collect(call):
            self.collects += 1
            if self.collects == self.fail_at:
                self.live_at_fault = [s.req.future
                                      for s in self.box["engine"]._active]
                raise errors.InternalError(None, None, "planted fault")
            return orig["collect"](call)

        def prefill_chunk(*a, **k):
            eng = self.box.get("engine")
            self.prefill_in_flight.append(
                eng is not None and eng._inflight is not None)
            return orig["prefill_chunk"](*a, **k)

        m.decode_async, m.collect, m.prefill_chunk = (decode_async, collect,
                                                      prefill_chunk)
        m.close = lambda: None
        return self

    def __exit__(self, *exc):
        for name in self._orig:
            delattr(self.model, name)
        return False


def _serve(model, prompts, budgets, eos, fail_at=None, timeouts=None,
           then=()):
    """Serve ``prompts`` through a pipelined engine, then the ``then``
    prompts once those are done; (results or the exceptions, on_token
    streams, the recording)."""
    box = {}
    model.eos_id = eos
    pol = serving.DecodePolicy(num_slots=SLOTS,
                               max_decode_len=model.max_seq_len,
                               bucket_sizes=model.decode_buckets,
                               max_new_tokens=max(budgets))
    streamed = [[] for _ in prompts]
    with _Recording(model, box, fail_at) as rec:
        with serving.GenerativeEngine("pipeline", model, pol) as eng:
            box["engine"] = eng
            rec.futures = [eng.generate(
                p, max_new_tokens=b,
                timeout_ms=(timeouts or {}).get(i),
                on_token=lambda t, lp, i=i: streamed[i].append((t, lp)))
                for i, (p, b) in enumerate(zip(prompts, budgets))]
            out = []
            for f in rec.futures:
                exc = f.exception(120)
                out.append(exc if exc is not None else f.result())
            out += [eng.generate(p, max_new_tokens=b).result(120)
                    for p, b in then]
    return out, streamed, rec


def test_streams_equal_serial_decoding(served):
    model, prompts, streams, eos = served
    out, streamed, rec = _serve(model, prompts, BUDGETS, eos)
    ended_early = 0
    for res, got, stream, budget in zip(out, streamed, streams, BUDGETS):
        want_toks, want_lps = _cut((stream[0][:budget], stream[1][:budget]),
                                   eos)
        assert res["tokens"].tolist() == want_toks
        np.testing.assert_allclose(res["logprobs"], want_lps, rtol=0,
                                   atol=1e-5)
        assert res["outcome"] == ("eos" if want_toks[-1] == eos else
                                  "length")
        # on_token saw exactly what the result holds: nothing past EOS
        assert [t for t, _ in got] == want_toks
        ended_early += len(want_toks) < budget
    assert ended_early >= 1


def test_a_sequence_at_its_budget_never_rides_the_next_step(served):
    """Each request rides one step a token it is given, plus one only
    when an end token (seen one step late) ended it: a sequence whose
    budget is spent is left out of the step enqueued next."""
    model, prompts, streams, eos = served
    out, _, rec = _serve(model, prompts, BUDGETS, eos)
    rides = {}          # first position -> steps ridden (prompts differ)
    live = {}           # slot -> the first position of its request
    for slots, positions, tokens in rec.steps:
        for s, p, t in zip(slots, positions, tokens):
            if t >= 0:                  # a request's first step
                live[s] = p
                rides[p] = 0
            assert p == live[s] + rides[live[s]]
            rides[live[s]] += 1
    assert sorted(rides) == sorted(len(p) - 1 for p in prompts)
    for res, prompt in zip(out, prompts):
        late = 1 if res["outcome"] == "eos" else 0
        assert len(res["tokens"]) <= rides[len(prompt) - 1] \
            <= len(res["tokens"]) + late


def test_a_finished_sequence_leaves_no_hole_while_requests_wait(served):
    """A sequence whose budget the enqueued step spends gives its slot back
    at that enqueue, so the joiner admitted next rides the step after:
    from the first full step to the last joiner's first, every step is
    full. Were the slot held until the token is fetched, the step enqueued
    meanwhile would carry a padded row (``decode_fill`` would drop)."""
    model, prompts, _, _ = served
    batch = prompts + _prompts(model, seed=5)
    budgets = [2 + i % 4 for i in range(len(batch))]       # staggered ends
    out, _, rec = _serve(model, batch, budgets, model.cfg.vocab_size + 1)
    assert [len(r["tokens"]) for r in out] == budgets
    rows = [len(slots) for slots, _, _ in rec.steps]
    joins = [i for i, (_, _, tokens) in enumerate(rec.steps)
             if any(t >= 0 for t in tokens)]
    first_full = rows.index(SLOTS)
    assert first_full < joins[-1]
    assert rows[first_full:joins[-1] + 1] == \
        [SLOTS] * (joins[-1] + 1 - first_full)


def test_nothing_is_delivered_past_an_end_token(served):
    """A request whose stream holds the end token early is cut there, and
    one step more ran for it than it got tokens: that step's token was
    dropped."""
    model, prompts, streams, eos = served
    which = [i for i, s in enumerate(streams)
             if eos in s[0][:BUDGETS[i] - 1]]
    assert which
    out, streamed, rec = _serve(model, prompts, BUDGETS, eos)
    for i in which:
        assert out[i]["outcome"] == "eos"
        assert out[i]["tokens"].tolist()[-1] == eos
        assert eos not in out[i]["tokens"].tolist()[:-1]
        assert len(streamed[i]) == len(out[i]["tokens"])
    # every step rode at most the four slots, and ended with none in flight
    assert all(len(slots) <= SLOTS for slots, _, _ in rec.steps)
    delivered = sum(len(r["tokens"]) for r in out)
    rode = sum(len(slots) for slots, _, _ in rec.steps)
    assert delivered <= rode <= delivered + len(which)


def test_joiners_are_admitted_while_a_step_is_in_flight(served):
    model, prompts, streams, eos = served
    overlap = monitoring.get_metric(
        "/stf/serving/decode_overlap").get_cell("pipeline")
    before = overlap.value()
    out, _, rec = _serve(model, prompts, BUDGETS, eos)
    assert any(rec.prefill_in_flight)
    after = overlap.value()
    steps = after["count"] - before["count"]
    assert steps == len(rec.steps)
    # every step but the first and those after an idle flush overlapped
    assert after["sum"] - before["sum"] >= steps - 2
    for res, stream, budget in zip(out, streams, BUDGETS):
        assert res["tokens"].tolist() == _cut(
            (stream[0][:budget], stream[1][:budget]), eos)[0]


def test_a_decode_position_opens_a_page_of_its_own(served):
    """The 9-token prompt's first decode position (8) and the 16-token
    prompt's second (16) lie in a page no prompt chunk filled: the engine
    allocates it before it enqueues the step that writes it."""
    model, prompts, streams, eos = served
    out, _, rec = _serve(model, prompts, BUDGETS, model.cfg.vocab_size + 1)
    positions = [p for _, pos, _ in rec.steps for p in pos]
    assert 8 in positions and 16 in positions
    for res, stream, budget in zip(out, streams, BUDGETS):
        assert res["tokens"].tolist() == stream[0][:budget]
        assert res["outcome"] == "length"


def test_an_expired_deadline_retires_its_sequence_alone(served):
    model, prompts, streams, eos = served
    # request 2 is due almost at once: it expires in the queue or after a
    # few tokens, and the others are served as without it
    out, streamed, _ = _serve(model, prompts, BUDGETS, eos,
                              timeouts={2: 1.0})
    assert isinstance(out[2], errors.DeadlineExceededError)
    assert [t for t, _ in streamed[2]] == \
        streams[2][0][:len(streamed[2])]
    for i, (res, stream, budget) in enumerate(zip(out, streams, BUDGETS)):
        if i != 2:
            assert res["tokens"].tolist() == _cut(
                (stream[0][:budget], stream[1][:budget]), eos)[0]


def test_a_device_error_at_collect_retires_the_rows_of_both_steps(served):
    """The third collect raises: every sequence live then — those of the
    step fetched and of the step in flight — fails with the error, the
    others are served whole, and so is a request that comes after."""
    model, prompts, streams, eos = served
    out, _, rec = _serve(model, prompts, BUDGETS, eos, fail_at=3,
                         then=[(prompts[0], BUDGETS[0])])
    failed = [i for i, r in enumerate(out)
              if isinstance(r, errors.InternalError)]
    assert failed == [i for i, f in enumerate(rec.futures)
                      if f in rec.live_at_fault]
    assert len(failed) >= 2 and "planted fault" in str(out[failed[0]])
    for i, res in enumerate(out):
        if i not in failed:
            j = i % len(prompts)
            stream, budget = streams[j], BUDGETS[j]
            assert res["tokens"].tolist() == _cut(
                (stream[0][:budget], stream[1][:budget]), eos)[0]


class _Compiles:
    """Backend compiles as jax.monitoring reports them, and plans the
    Session made (its executable-cache misses)."""

    def __init__(self):
        self.n = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, secs, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.n += 1

    @staticmethod
    def plans():
        cells = monitoring.get_metric(
            "/stf/session/executable_cache/misses").cells()
        return sum(c.value() for c in cells.values())

    def mark(self):
        return self.n, self.plans()

    def since(self, mark):
        return self.n - mark[0], self.plans() - mark[1]


_CLOCK = []


# plans at build: the initializer, the allocation, decode buckets 1 and 6,
# prefill buckets 1 and 2, copy-on-write — as many as before the
# last-token buffer; and no more backend compiles than then (fewer when
# this process or the persistent compile cache holds some). A decode
# bucket no prefill bucket shares, of a size no other test here uses,
# shows a per-shape computation the decode programs alone would add
@pytest.mark.parametrize("which,plans,compiles", [("causal_lm", 7, 12),
                                                  ("state_space", 7, 7)])
def test_build_and_a_burst_add_no_set_up_work(which, plans, compiles):
    if not _CLOCK:
        _CLOCK.append(_Compiles())
    clock = _CLOCK[0]
    mark = clock.mark()
    model = _model(which, aot_warmup=True, decode=(1, 6), prefill=(1, 2),
                   max_live=6)
    try:
        compiled, planned = clock.since(mark)
        assert planned == plans
        assert compiled <= compiles
        mark = clock.mark()
        # a burst as the benchmark's warm-up runs it: every request two
        # tokens, each bucket's first execution
        prompts = _prompts(model, seed=1)
        out, _, _ = _serve(model, prompts, (2,) * len(prompts),
                           model.cfg.vocab_size + 1)
        assert [len(r["tokens"]) for r in out] == [2] * len(prompts)
        assert clock.since(mark) == (0, 0)
    finally:
        model.close()


def test_the_last_result_waits_for_nothing():
    """An engine whose last request finishes returns it at once: the step
    in flight is fetched when nothing is left to enqueue, not when the
    next request comes or a timeout passes."""
    model = _model("causal_lm")
    try:
        prompt = _prompts(model)[0]
        pol = serving.DecodePolicy(num_slots=SLOTS,
                                   max_decode_len=model.max_seq_len,
                                   bucket_sizes=model.decode_buckets,
                                   max_new_tokens=5)
        with serving.GenerativeEngine("pipeline_last", model, pol) as eng:
            eng.generate(prompt).result(120)        # compiled
            t0 = time.perf_counter()
            res = eng.generate(prompt).result(120)
            took = time.perf_counter() - t0
            # the engine is back waiting on its queue with nothing in
            # flight
            deadline = time.perf_counter() + 10
            while eng._inflight is not None and \
                    time.perf_counter() < deadline:
                time.sleep(0.001)
            assert eng._inflight is None and not eng._active
        assert len(res["tokens"]) == 5
        # six steps of a toy model on the CPU: far below any poll
        assert took < 5.0
    finally:
        model.close()
