"""The drive of a serving cell whose reference is a module of its own,
shared by the runners that have one (``serve_hybrid_linear_moe.py`` first):
``run`` (``run.py``), ``calibrate`` (``calibrate.py``), the check against
the reference and the planted second-best token, each given the runner
module ``m``, which names ``ref`` (the reference module, with
``served_token_gaps``), ``load_weights(model, config, seed)``,
``traced_work(spec, reqs, t0, t1)`` and ``check``. What a runner has of its
own is its weights and its traced stretch's work; the four runners before
it keep their own copies of this drive (PERF.md section 7, item (z)).

From ``serve.py`` the request record, model build, server start, warm-up,
the one order of every block, the window, settling, the window's token
count and the sample; from ``serve_sparse_moe.py`` the comparison's
numbers (``numbers_of``), their shapes for the log.
"""

from __future__ import annotations

import gc
import time

import numpy as np

from chipbench import harness, traffic as traffic_mod
from chipbench.compare import against, is_correct
from chipbench.runners.serve import (
    _Request, _engine_row, _percentile, build, drive, in_one_order,
    sample_finished, settle, start_server, tokens_in_window, warm_up,
    window_log)
from chipbench.runners.serve_sparse_moe import (
    _gap_shape, _logprob_diffs, _spread, numbers_of)


def check(ref, config, seed, sample, eos_id, control=None, timings=None):
    """The reference, once over each sampled prompt with its served
    tokens, by the reference module ``ref``. Returns (rows per request,
    answers that are cut short)."""
    if not sample:
        return [], 1
    missing = sum(not r.complete(eos_id) for r in sample)
    rows = ref.served_token_gaps(
        config["reference"]["spec"], seed, [r.prompt for r in sample],
        [r.tokens for r in sample], control=control, timings=timings)
    for row, r in zip(rows, sample):
        row["served_logprob"] = np.asarray(r.logprobs, np.float64)
    return rows, missing


def second_best_fault(m, config, seed, sample, rows, eos_id):
    """``serve.second_best_fault`` against the runner ``m``'s ``check``: in each
    sampled answer one token, at a place drawn from the seed, is replaced
    by the token the reference puts second there, with that token's own
    correct log-probability."""
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
    rows2, missing = m.check(config, seed, altered, eos_id)
    for row2, j in zip(rows2, places):
        row2["served_logprob"][j] = row2["logprob"][j]
    return (numbers_of(rows2, missing),
            [float(row["margin"][j]) for row, j in zip(rows, places)])


def run(ctx, m):
    """A cell's run (``run.py``) for the runner ``m``."""
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
    m.load_weights(model, config, args.seed)
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
        facts["work"] = m.traced_work(spec, reqs, tracer.t0, tracer.t1)

    first = sorted(r.times[0] - t_open for r in attempted)
    info = {
        "requests": {"offered": len(reqs), "taken_up": len(attempted),
                     "finished": len(finished),
                     "finished_in_window": sum(
                         r.times[-1] <= t_close for r in finished),
                     "ended_before_budget": sum(
                         len(r.tokens) < r.budget for r in finished),
                     "tokens_in_window": in_window,
                     "tokens_delivered_in_window": sum(
                         ts <= t_close for r in reqs for ts in r.times),
                     "prompt_tokens_taken_up": sum(
                         len(r.prompt) for r in attempted),
                     "window_s": t_close - t_open},
        "generator_lateness_ms": {
            "p95": 1000 * _percentile(win["lateness"], 95),
            "max": 1000 * max(win["lateness"])},
        "queue_depth": {"window_start": at_open["depth"],
                        "window_end": row1["queue_depth"],
                        "slots_active_end": row1["slots_active"]},
        "prefix_cache": row1.get("prefix_cache"),
        # when the slots first all held a started answer
        "first_fill_s": (first[min(len(first), model.num_slots) - 1]
                         if first else None),
        # in the order offered: two runs part where these do
        "first_token_s": [r.times[0] - t_open for r in attempted],
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
    timings["reference"] = {}
    rows, missing = m.check(config, args.seed, sample, eos_id,
                          timings=timings["reference"])
    timings["reference_s"] = time.perf_counter() - t
    numbers = numbers_of(rows, missing)
    info["checked"] = {"requests": len(sample),
                       "tokens": int(sum(len(r["gap"]) for r in rows)),
                       "longest": (len(sample[0].prompt)
                                   + len(sample[0].tokens)) if sample else 0,
                       "numbers": numbers,
                       "per_request": [_gap_shape(r, row)
                                       for r, row in zip(sample, rows)]}
    compared = against(numbers, ctx["limits"])
    return {"attempted": len(attempted), "failed": len(failed),
            "end_to_end": end_to_end, "compared": compared, "facts": facts,
            "memory_peak_bytes": peak_bytes, "info": info}


def calibrate(ctx, seeds, n_control, seconds, m):
    """The comparison's readings over many seeds in one process (see
    chipbench/calibrate.py), as ``serve_latent_moe.calibrate`` takes them:
    the program and the reference cannot share the chip's memory, so every
    seed builds the model anew and frees it before its reference runs."""
    config, mix, limits = ctx["config"], ctx["traffic"], ctx["limits"]
    spec, name = config["reference"]["spec"], ctx["model_name"]
    for i, seed in enumerate(seeds):
        t = time.perf_counter()
        model = build(config)
        m.load_weights(model, config, seed)
        server = start_server(model, config, name)
        warm_up(server, name, model, spec["vocab"])
        reqs = in_one_order([_Request(r) for r in traffic_mod.requests(
            mix, spec["vocab"], seed, seconds)], mix)
        win = drive(server, name, mix, reqs, seconds)
        settle(reqs, win["deadline"])
        eos_id = model.eos_id
        server.close()
        del server, model
        gc.collect()
        finished = [r for r in reqs if r.outcome() == "ok"]
        sample = sample_finished(finished, seed, mix["check_requests"])
        control = config["control_precision"] if i < n_control else None
        rows, missing = m.check(config, seed, sample, eos_id, control)
        numbers = numbers_of(rows, missing)
        record = {"seed": seed, "program": numbers,
                  "logprob_gap_mean_p50_p90_p99": {
                      "program": _spread(_logprob_diffs(rows))},
                  "passes": {"program": is_correct(against(numbers, limits))},
                  "finished": len(finished), "checked": len(sample),
                  "tokens": int(sum(len(r["gap"]) for r in rows)),
                  "margin_percentiles_1_5_50": [
                      float(x) for x in np.percentile(np.concatenate(
                          [r["margin"] for r in rows]), [1, 5, 50])]
                  if rows else None}
        if control and rows:
            record["control"] = numbers_of(rows, 0, control=True)
            record["logprob_gap_mean_p50_p90_p99"]["control"] = _spread(
                _logprob_diffs(rows, control=True))
            record["second_best"], record["second_best_margins"] = \
                second_best_fault(m, config, seed, sample, rows, eos_id)
            for label in ("control", "second_best"):
                record["passes"][label] = is_correct(
                    against(record[label], limits))
        record["seconds"] = time.perf_counter() - t
        harness.log(calibrate=record)
