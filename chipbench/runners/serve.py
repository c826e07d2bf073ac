"""Runner ``serve``: drives ``ModelServer.generate(prompt, on_token=)``.

Builds the configuration's generative model through its normal entry point,
loads the seed's weights into it, loads it into a ModelServer, warms every
compiled shape, then offers the traffic file's backlog (everything due as
the window opens, every block of sizes in one order on every seed) from
this one thread, which then sleeps until the window closes. Tokens are
stamped on the host clock as ``on_token`` delivers them. After the window
has closed and the server is gone, the reference runs once over a seeded
sample of finished requests.
"""

from __future__ import annotations

import collections
import contextlib
import gc
import time

import numpy as np

from chipbench import harness, traffic as traffic_mod, work
from chipbench.compare import against, is_correct, serve_numbers


class _Request:
    __slots__ = ("due", "prompt", "budget", "times", "tokens", "logprobs",
                 "future", "submitted")

    def __init__(self, spec):
        self.due = spec["due"]
        self.prompt = spec["prompt"]
        self.budget = spec["max_new_tokens"]
        self.times, self.tokens, self.logprobs = [], [], []
        self.future = self.submitted = None

    def on_token(self, tok, logp):
        """Where an answer's tokens are produced for the harness (a test
        alters one here and sees ``correct`` come out false)."""
        self.times.append(time.perf_counter())
        self.tokens.append(int(tok))
        self.logprobs.append(float(logp))

    def outcome(self):
        """"ok", "pending" or the error's name, judged by what came."""
        if self.future is None or not self.future.done():
            return "pending"
        exc = self.future.exception(timeout=0)
        return "ok" if exc is None else type(exc).__name__

    def complete(self, eos_id):
        return bool(self.tokens) and (len(self.tokens) == self.budget
                                      or self.tokens[-1] == eos_id)


def build(config):
    import simple_tensorflow_tpu as stf

    prog = config["program"]
    harness.set_kernel_mode(config)
    cfg = harness.import_attr(prog["config_class"])(**prog["config_kwargs"])
    return harness.import_attr(prog["model_class"])(
        cfg, compute_dtype=getattr(stf, prog["compute_dtype"]),
        init_fresh=True, seed=0, **prog["model_kwargs"])


def load_weights(model, config, params):
    """The seed's weights into the served model's variables."""
    import simple_tensorflow_tpu as stf

    with model.graph.as_default():
        variables = harness.map_variables(config, stf.trainable_variables())
    harness.load_variables(model.session, variables, params)


def start_server(model, config, name):
    from simple_tensorflow_tpu import serving

    policy = serving.DecodePolicy(
        num_slots=model.num_slots, max_decode_len=model.max_seq_len,
        bucket_sizes=model.decode_buckets,
        prefill_bucket_sizes=model.prefill_buckets,
        max_queue_depth=config["program"]["max_queue_depth"])
    server = serving.ModelServer()
    server.load_generative(model, name, policy=policy)
    return server


def _submit(server, name, req, deadline):
    req.submitted = time.perf_counter()
    req.future = server.generate(
        req.prompt, model=name, max_new_tokens=req.budget,
        timeout_ms=max(deadline - req.submitted, 0.001) * 1000.0,
        on_token=req.on_token)


def warm_up(server, name, model, vocab):
    """Run every compiled shape once (all are compiled when the model is
    built; this pays each one's first execution): one burst per bucket
    size, as wide as the bucket, of two-page prompts and two tokens."""
    rng = np.random.default_rng(0)
    deadline = time.perf_counter() + 300
    for width in sorted(set(model.decode_buckets)
                        | set(model.prefill_buckets)):
        reqs = [_Request({
            "due": 0.0, "max_new_tokens": 2,
            "prompt": rng.integers(2, vocab, size=model.page_len + 3 + i % 5
                                   ).astype(np.int32)})
                for i in range(width)]
        for r in reqs:
            _submit(server, name, r, deadline)
        for r in reqs:
            r.future.result(timeout=300)


def _engine_row(server, name):
    return [r for r in server.statusz_info() if r.get("model") == name][0]


def _percentile(values, q):
    return float(np.percentile(np.asarray(values, np.float64), q))


def in_one_order(reqs, mix):
    """The generator's requests with every block in ONE order on every
    seed: the order is drawn from ``shape_seed`` as the sizes' pairing is
    (the block's middle pair still heads the queue); token ids and
    weights stay the seed's. WHICH prompts of a block a window admits,
    and when, ``traffic.requests`` leaves to the seed's shuffle, and the
    rate follows it: where one admission stalls every answer for a long
    prompt's whole prefill the seeds read 10 % apart against 0.3-0.6 % on
    one seed (``longdoc-backlog``, PR 27); on ``backlog``'s short prompts
    three seeds' means lay 3.1 % apart where a seed repeated within 1.3 %
    (PR 32; PERF.md)."""
    block = mix["block"]
    rng = np.random.default_rng([int(mix["shape_seed"]), 4])
    out = []
    for b in range(0, len(reqs), block):
        by_size = sorted(reqs[b:b + block],
                         key=lambda r: (len(r.prompt), r.budget))
        order = rng.permutation(len(by_size))
        if b == 0:
            head = int(np.flatnonzero(order == len(by_size) // 2)[0])
            order[[0, head]] = order[[head, 0]]
        out += [by_size[j] for j in order]
    return out


class _Pauses:
    """The garbage collector's pauses while it is installed, from
    ``gc.callbacks``: (start on the host clock, seconds, generation). A
    collection runs on whichever thread's allocation set it off and holds
    the interpreter for its length, the engine's thread with it."""

    def __init__(self):
        self.records = []
        self._start = None

    def __call__(self, phase, info):
        if phase == "start":
            self._start = time.perf_counter()
        elif self._start is not None:
            self.records.append((self._start,
                                 time.perf_counter() - self._start,
                                 info["generation"]))
            self._start = None

    def __enter__(self):
        gc.callbacks.append(self)
        return self

    def __exit__(self, *exc):
        gc.callbacks.remove(self)


def _sleep_until(t):
    """One sleep as a rule (``time.sleep`` never returns early on a Python
    that sleeps on the monotonic clock); the loop only guards the rule."""
    while (wait := t - time.perf_counter()) > 0:
        time.sleep(wait)


def drive(server, name, mix, reqs, seconds, tracer=None, on_open=None,
          spans=None):
    """The window: every request is due as it opens and is offered at
    once, from this one thread; then SLEEP until the close. Returns the
    window's facts; the requests carry their own times and tokens. The
    harness's spans (``submit``, ``wait_request``) label idle gaps.

    This thread wakes at most three times in a window: where a trace is
    asked for, at its start and at its end, and at the close. A wake-up
    asks the engine's thread for the interpreter, and the host sets this
    window's pace: a poll every 2 ms was 20,000 of them (PERF.md)."""
    span = spans.span if spans is not None else (
        lambda _label: contextlib.nullcontext())
    tracing = tracer is not None and tracer.enabled
    with _Pauses() as pauses:
        t_open = time.perf_counter()
        t_close = t_open + seconds
        deadline = t_close + mix["drain_seconds"]
        if on_open is not None:
            on_open()
        if tracer is not None:
            tracer.arm()
        with span("submit"):
            for r in reqs:
                _submit(server, name, r, deadline)
        with span("wait_request"):
            if tracing:
                _sleep_until(min(tracer.t_open + tracer.start_after,
                                 t_close))
                tracer.poll()  # starts the trace, if arm() did not
                if tracer.t0 is not None:
                    _sleep_until(min(tracer.t0 + tracer.seconds, t_close))
                    tracer.poll()  # stops it once its seconds are over
            _sleep_until(t_close)
        if tracer is not None:
            tracer.stop()
    return {"t_open": t_open, "t_close": t_close, "deadline": deadline,
            "lateness": [r.submitted - t_open for r in reqs],
            "gc_pauses": pauses.records}


def _decode_deliveries(reqs, t_open, t_close):
    """When each engine step inside the window delivered its tokens: a
    step hands every live answer one token within a fraction of a
    millisecond, and two steps lie 10 ms or more apart, so the sorted
    token times split into steps wherever two lie over 1 ms apart."""
    times = np.sort(np.fromiter(
        (ts for r in reqs for ts in r.times if t_open <= ts <= t_close),
        np.float64))
    if not len(times):
        return times
    return times[np.concatenate([[True], np.diff(times) > 1e-3])]


def window_log(reqs, win, longest=20):
    """For a run's ``info``: what stalled the window, if anything did.
    The collector's pauses inside it (count by generation, their sum and
    the longest, and every pause over 5 ms with its second of the window),
    and the gaps between two consecutive decode deliveries (the median,
    the longest, how many lie over 1.5x the median, and the ``longest`` of
    those with their second of the window, in the window's order) — what
    the training runner prints of its steps. Nothing here is a metric."""
    t_open, t_close = win["t_open"], win["t_close"]
    pauses = [p for p in win["gc_pauses"] if t_open <= p[0] <= t_close]
    collector = {
        "enabled": gc.isenabled(),
        "collections": dict(collections.Counter(
            str(generation) for _, _, generation in pauses)),
        "paused_ms": 1000 * sum(d for _, d, _ in pauses),
        "longest_ms": 1000 * max((d for _, d, _ in pauses), default=0.0),
        "over_5ms": [[round(t - t_open, 3), round(1000 * d, 2), g]
                     for t, d, g in pauses if d > 0.005][:longest]}
    steps = _decode_deliveries(reqs, t_open, t_close)
    gaps = np.diff(steps)
    if not len(gaps):
        return {"collector": collector, "decode_gaps_ms": None}
    median = float(np.median(gaps))
    over = np.flatnonzero(gaps > 1.5 * median)
    kept = np.sort(over[np.argsort(gaps[over])[-longest:]])
    return {"collector": collector, "decode_gaps_ms": {
        "deliveries": len(steps), "median": 1000 * median,
        "p95": 1000 * _percentile(gaps, 95),
        "longest": 1000 * float(gaps.max()),
        "over_1.5x_median": len(over),
        "longest_over_1.5x": [[round(float(steps[i] - t_open), 3),
                               round(1000 * float(gaps[i]), 1)]
                              for i in kept]}}


def settle(reqs, deadline, everything=False):
    """Wait for every answer the engine took up, until the harness's
    deadline (``drain_seconds`` past the close), which is also how a
    backlog's run ends: what the engine never took up is left to expire,
    unless ``everything`` asks to see the queue empty (the next seed of a
    calibration needs an idle engine)."""
    for r in reqs:
        if not r.times and not everything:
            continue  # never taken up: ended by its deadline
        try:
            r.future.result(timeout=max(deadline + 2 - time.perf_counter(),
                                        0.01))
        except Exception:  # noqa: BLE001 — judged by outcome()
            pass


def tokens_in_window(reqs, t_open, t_close):
    """Tokens the window [t_open, t_close] produced: every token delivered
    in it, and of each answer's token that was in flight at the close the
    share of its wait that lay inside the window (from the answer's
    token before it, delivered in the window, to its own delivery after
    the close). An engine step delivers one token per live answer at
    once, 1 % of a window's count, and the steps are uneven (a step that
    admits a cohort of prompts is several times a plain one): counting
    whole steps only, or stretching the window to the next delivery, makes
    the rate jump by 1-5 % on whether a delivery falls a millisecond
    before or after the close (PERF.md). A first token in flight gets no
    share: its prefill may not have begun."""
    total = 0.0
    for r in reqs:
        before = [ts for ts in r.times if ts <= t_close]
        total += len(before)
        if before and len(before) < len(r.times) and before[-1] >= t_open:
            total += (t_close - before[-1]) / (r.times[len(before)]
                                               - before[-1])
    return total


def sample_finished(finished, seed, k):
    """A sample of the finished requests, drawn from the seed, with the
    longest in it."""
    if not finished:
        return []
    rng = np.random.default_rng([int(seed), 4])
    longest = max(finished, key=lambda r: len(r.prompt) + len(r.tokens))
    others = [r for r in finished if r is not longest]
    pick = rng.permutation(len(others))[:k - 1]
    return [longest] + [others[i] for i in pick]


def check(config, seed, sample, eos_id, control=None):
    """The reference, once over each sampled prompt with its served
    tokens. Returns (rows per request, answers that are cut short)."""
    from chipbench.reference import postln_transformer as ref

    spec = config["reference"]["spec"]
    kwargs = config["program"]["model_kwargs"]
    if not sample:
        return [], 1
    missing = sum(not r.complete(eos_id) for r in sample)
    rows = ref.served_token_gaps(
        spec, ref.init_params(spec, seed),
        [r.prompt for r in sample], [r.tokens for r in sample],
        pad_to=kwargs["page_len"] * kwargs["pages_per_seq"],
        n_out=config["reference"]["max_output"], control=control)
    for row, r in zip(rows, sample):
        row["served_logprob"] = np.asarray(r.logprobs, np.float64)
    return rows, missing


def run(ctx):
    from chipbench.reference import postln_transformer as ref

    config, mix, args = ctx["config"], ctx["traffic"], ctx["args"]
    spec = config["reference"]["spec"]
    name = ctx["model_name"]
    clock, tracer = ctx["clock"], ctx["tracer"]
    timings = {}

    t = time.perf_counter()
    mark = clock.mark()
    model = build(config)
    timings["build_and_compile_s"] = time.perf_counter() - t
    t = time.perf_counter()
    load_weights(model, config, ref.init_params(spec, args.seed))
    timings["init_s"] = time.perf_counter() - t
    server = start_server(model, config, name)
    t = time.perf_counter()
    warm_up(server, name, model, spec["vocab"])
    timings["warm_up_s"] = time.perf_counter() - t
    timings["setup_compiles"] = clock.since(mark)

    reqs = in_one_order([_Request(r) for r in traffic_mod.requests(
        mix, spec["vocab"], args.seed, args.seconds)], mix)
    at_open = {}

    def on_open():
        at_open["counters"] = ctx["snapshot_counters"]()
        at_open["depth"] = _engine_row(server, name)["queue_depth"]
        at_open["mark"] = clock.mark()
        at_open["setup_s"] = time.perf_counter() - ctx["t_start"]

    win = drive(server, name, mix, reqs, args.seconds, tracer, on_open,
                ctx["spans"])
    t_open, t_close, deadline = win["t_open"], win["t_close"], win["deadline"]
    counters1 = ctx["snapshot_counters"]()
    row1 = _engine_row(server, name)
    window_compiles = clock.since(at_open["mark"])
    peak_bytes = harness.memory_peak_bytes(ctx["devices"])

    settle(reqs, deadline)
    t = time.perf_counter()
    server.close()
    timings["close_s"] = time.perf_counter() - t

    in_window = tokens_in_window(reqs, t_open, t_close)
    finished = [r for r in reqs if r.outcome() == "ok"]
    # a backlog's requests are attempted once the engine takes them up;
    # the deadline that ends the run is the harness's own
    attempted = [r for r in reqs if r.times]
    failed = [r for r in attempted if r.outcome() not in
              ("ok", "DeadlineExceededError")]
    end_to_end = {"serve_tokens_per_s": in_window / (t_close - t_open),
                  "setup_s": at_open["setup_s"]}

    facts = {"work": {}, "counters": {k: (at_open["counters"][k],
                                          counters1[k])
                                      for k in counters1}}
    if tracer.t1 is not None:
        facts["work"] = traced_work(spec, reqs, tracer.t0, tracer.t1)

    lateness = win["lateness"]
    info = {
        "requests": {"offered": len(reqs), "taken_up": len(attempted),
                     "finished": len(finished),
                     "finished_in_window_per_s": sum(
                         r.times[-1] <= t_close for r in finished)
                     / (t_close - t_open),
                     "tokens_in_window": in_window,
                     "tokens_delivered_in_window": sum(
                         ts <= t_close for r in reqs for ts in r.times),
                     "window_s": t_close - t_open},
        "generator_lateness_ms": {
            "p50": 1000 * _percentile(lateness, 50),
            "p95": 1000 * _percentile(lateness, 95),
            "max": 1000 * max(lateness)} if lateness else None,
        "queue_depth": {"window_start": at_open["depth"],
                        "window_end": row1["queue_depth"],
                        "slots_active_end": row1["slots_active"]},
        "prefix_cache": row1.get("prefix_cache"),
        "first_token_ms_p50": (1000 * _percentile(
            [r.times[0] - t_open for r in attempted], 50)
            if attempted else None),
        "timings": timings, "window_compiles": window_compiles,
        "kernel_routing": harness.kernel_routing(),
        **window_log(reqs, win),
    }

    # -- the reference, once the program is gone ------------------------------
    eos_id = model.eos_id
    del server, model
    gc.collect()
    t = time.perf_counter()
    sample = sample_finished(finished, args.seed, mix["check_requests"])
    rows, missing = check(config, args.seed, sample, eos_id)
    timings["reference_s"] = time.perf_counter() - t
    numbers = serve_numbers(rows, missing)
    info["checked"] = {"requests": len(sample),
                       "tokens": int(sum(len(r["gap"]) for r in rows)),
                       "longest": (len(sample[0].prompt)
                                   + len(sample[0].tokens)) if sample else 0,
                       "numbers": numbers}
    compared = against(numbers, ctx["limits"])
    return {"attempted": len(attempted), "failed": len(failed),
            "end_to_end": end_to_end, "compared": compared, "facts": facts,
            "memory_peak_bytes": peak_bytes, "info": info}


def second_best_fault(config, seed, sample, rows, eos_id):
    """The planted fault for ``logit_gap``: in each sampled answer one
    token, at a place drawn from the seed, is replaced where it is
    produced by the token the reference puts second there, with that
    token's own correct log-probability; the server went on from its own
    token. Returns the comparison's numbers for the altered answers, and
    the reference's margins at the altered places."""
    rng = np.random.default_rng([int(seed), 5])
    altered, places = [], []
    for r, row in zip(sample, rows):
        j = int(rng.integers(len(r.tokens)))
        twin = _Request({"due": 0.0, "prompt": r.prompt,
                         "max_new_tokens": r.budget})
        twin.tokens = list(r.tokens)
        twin.tokens[j] = int(row["second"][j])
        twin.logprobs = list(r.logprobs)
        altered.append(twin)
        places.append(j)
    rows2, missing = check(config, seed, altered, eos_id)
    for row2, j in zip(rows2, places):
        row2["served_logprob"][j] = row2["logprob"][j]
    return (serve_numbers(rows2, missing),
            [float(row["margin"][j]) for row, j in zip(rows, places)])


def calibrate(ctx, seeds, n_control, seconds):
    """The comparison's readings over many seeds in one process (see
    chipbench/calibrate.py): one model, and for every seed its weights, a
    fresh window at the cell's own load, and the reference over the
    sample a run would take; on the first ``n_control`` seeds the
    control's readings at the same positions and the planted second-best
    fault's too, each also put through the cell's limits (``passes``)."""
    from chipbench.reference import postln_transformer as ref

    config, mix, limits = ctx["config"], ctx["traffic"], ctx["limits"]
    spec, name = config["reference"]["spec"], ctx["model_name"]
    model = build(config)
    server = start_server(model, config, name)
    warm_up(server, name, model, spec["vocab"])
    for i, seed in enumerate(seeds):
        t = time.perf_counter()
        # the engine is idle here: every request of the seed before has
        # ended, by its answer or by the harness's deadline
        load_weights(model, config, ref.init_params(spec, seed))
        reqs = in_one_order([_Request(r) for r in traffic_mod.requests(
            mix, spec["vocab"], seed, seconds)], mix)
        win = drive(server, name, mix, reqs, seconds)
        settle(reqs, win["deadline"], everything=True)
        finished = [r for r in reqs if r.outcome() == "ok"]
        sample = sample_finished(finished, seed, mix["check_requests"])
        control = config["control_precision"] if i < n_control else None
        rows, missing = check(config, seed, sample, model.eos_id, control)
        numbers = serve_numbers(rows, missing)
        record = {"seed": seed, "program": numbers,
                  "passes": {"program": is_correct(against(numbers, limits))},
                  "finished": len(finished), "checked": len(sample),
                  "tokens": int(sum(len(r["gap"]) for r in rows)),
                  "margin_percentiles_1_5_50": [
                      float(x) for x in np.percentile(np.concatenate(
                          [r["margin"] for r in rows]), [1, 5, 50])]}
        if control:
            record["control"] = serve_numbers(rows, 0, control=True)
            record["second_best"], record["second_best_margins"] = \
                second_best_fault(config, seed, sample, rows, model.eos_id)
            for label in ("control", "second_best"):
                record["passes"][label] = is_correct(
                    against(record[label], limits))
        record["seconds"] = time.perf_counter() - t
        harness.log(calibrate=record)
    server.close()


def traced_work(spec, reqs, t0, t1):
    """The FLOPs of what was processed inside the traced window [t0, t1],
    counted with chipbench/work.py: every token delivered in it (one
    decode position over its context), and the prompt of every request
    whose first token came in it (admission runs a prompt's page chunks to
    completion right before the step that emits its first token)."""
    model_flops = 0.0
    decode_tokens = prompts = 0
    for r in reqs:
        plen = len(r.prompt)
        for j, ts in enumerate(r.times):
            if t0 <= ts < t1:
                decode_tokens += 1
                model_flops += work.causal_lm_decode_flops(spec, plen + j)
        if r.times and t0 <= r.times[0] < t1:
            prompts += 1
            model_flops += work.causal_lm_prompt_flops(spec, plen - 1)
    return {"model_flops": model_flops, "decode_tokens": decode_tokens,
            "prompts": prompts}
