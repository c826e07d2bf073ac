"""Runner ``train``: drives ``Session.run([train_op, loss], feed_dict)``.

Set-up builds ONE object — the graph, the Session and its compiled step —
loads the seed's weights into it, drives it through the job's first
``check_steps`` steps on the window's own call and feed (reading the Adam
first-moment state after step 1 and the parameters after the last), and
hands that same Session to the window. The reference follows those steps
once the window has closed and the program's state is freed.
"""

from __future__ import annotations

import gc
import re
import time

import numpy as np

from chipbench import harness, traffic as traffic_mod, work
from chipbench.compare import against, is_correct, train_numbers


def build(config, job):
    """Graph through the configuration's entry point; returns the
    builder's dict (placeholders, loss, train_op)."""
    import simple_tensorflow_tpu as stf

    prog = config["program"]
    harness.set_kernel_mode(config)
    cfg = harness.import_attr(prog["config_class"])(**prog["config_kwargs"])
    builder = harness.import_attr(prog["builder"])
    stf.reset_default_graph()
    return builder(batch_size=job["batch"], seq_len=job["seq_len"],
                   max_predictions=job["masked_per_row"], cfg=cfg,
                   learning_rate=job["learning_rate"],
                   compute_dtype=getattr(stf, prog["compute_dtype"]),
                   **prog.get("builder_kwargs", {}))


def program_variables(config):
    """{reference leaf: program Variable} for the default graph."""
    import simple_tensorflow_tpu as stf

    return harness.map_variables(config, stf.trainable_variables())


def load_weights(sess, variables, params):
    """The seed's weights into the program's variables, and the program's
    own initial values into the rest (optimizer slots, step counters): the
    program's random initializers never run. Returns the host copies
    (float32) keyed like ``variables``."""
    import simple_tensorflow_tpu as stf

    loaded = {v.name for v in variables.values()}
    sess.run(stf.variables_initializer(
        [v for v in stf.global_variables() if v.name not in loaded]))
    return harness.load_variables(sess, variables, params)


def adam_first_moments(sess, variables):
    """{leaf: Adam's m for that variable} from the optimizer's state, in
    either layout the program has: flat per-dtype groups
    (``fused_m_g<i>``, variables in creation order within a group) or one
    slot per variable (``<variable>/Adam``)."""
    import simple_tensorflow_tpu as stf

    every = {v.name.split(":")[0]: v for v in stf.global_variables()}
    flat = sorted((int(m.group(1)), v) for n, v in every.items()
                  for m in [re.search(r"fused_m_g(\d+)$", n)] if m)
    leaf_of = {v.name: k for k, v in variables.items()}
    out = {}
    if flat:
        groups = {}
        for v in stf.trainable_variables():
            groups.setdefault(v.dtype.base_dtype.name, []).append(v)
        values = sess.run([v for _, v in flat])
        for members, value in zip(groups.values(), values):
            value = np.asarray(value, np.float32)
            sizes = [int(np.prod(v.shape.as_list())) for v in members]
            if sum(sizes) != value.size:
                raise RuntimeError("flat Adam slot does not match its group")
            for v, part in zip(members, np.split(value,
                                                 np.cumsum(sizes)[:-1])):
                out[leaf_of[v.name]] = part
        return out
    slots = [every[v.name.split(":")[0] + "/Adam"]
             for v in variables.values()]
    for leaf, value in zip(variables, sess.run(slots)):
        out[leaf] = np.asarray(value, np.float32).reshape(-1)
    return out


def _step(sess, fetches, feed):
    """The timed call: the window and the first steps both go through it
    (and a test breaks the path underneath it)."""
    return sess.run(fetches, feed_dict=feed)


def _norms(tree):
    return {k: float(np.sqrt(np.sum(np.square(v, dtype=np.float64))))
            for k, v in tree.items()}


def first_steps(sess, model, variables, feeds, job, init):
    """From the seed's weights (``init``, as :func:`load_weights` left
    them) drive the job's first ``check_steps`` steps through the
    window's own call and feed. Returns what the
    comparison needs of the program: each step's loss, the norm of the
    first gradient as Adam got it (its first moment after one step, over
    1 - beta1) and the norm of the parameters' change, per leaf."""
    fetches = [model["train_op"], model["loss"]]
    losses, grad1, grad1_full = [], None, None
    for i in range(job["check_steps"]):
        _, loss = _step(sess, fetches, feeds[i % len(feeds)])
        losses.append(float(loss))
        if i == 0:
            b1 = job["beta1"]
            grad1_full = {k: m / (1.0 - b1) for k, m in
                          adam_first_moments(sess, variables).items()}
            grad1 = _norms(grad1_full)
    after = sess.run(list(variables.values()))
    change = _norms({k: np.asarray(a, np.float32) - init[k]
                     for k, a in zip(variables, after)})
    return {"losses": losses, "grad1_norms": grad1, "change_norms": change,
            "grad1": grad1_full}


def calibrate(ctx, seeds, n_control, _seconds=0.0):
    """The comparison's readings over many seeds in one process (see
    chipbench/calibrate.py): the program's on every seed, the control's
    and the planted half-batch fault's on the first ``n_control``, each
    also put through the cell's limits as a run would (``passes``: the
    program has to, the control and the fault must not). A state left
    unchanged reads 1 by the comparison's measure and needs no run."""
    import simple_tensorflow_tpu as stf
    from chipbench.reference import postln_transformer as ref

    config, job, limits = ctx["config"], ctx["traffic"], ctx["limits"]
    spec = config["reference"]["spec"]
    model = build(config, job)
    sess = stf.Session()
    variables = program_variables(config)
    n_check = job["check_steps"]
    for i, seed in enumerate(seeds):
        t = time.perf_counter()
        batches = traffic_mod.train_batches(job, spec, seed)[:n_check]
        feeds = [{model[k]: v for k, v in b.items()} for b in batches]
        init = load_weights(sess, variables, ref.init_params(spec, seed))
        got = first_steps(sess, model, variables, feeds, job, init)
        del init
        want = ref.bert_train_reference(spec, seed, batches, job)
        numbers, leaves = train_numbers(got, want)
        record = {"seed": seed, "program": numbers, "leaves": leaves,
                  "passes": {"program": is_correct(against(numbers, limits))},
                  "losses": got["losses"], "reference_losses": want["losses"]}
        if i < n_control:
            for label, kwargs in (
                    ("control", {"precision": config["control_precision"]}),
                    ("half_batch", {"fault": "half_batch"})):
                other = ref.bert_train_reference(
                    spec, seed, batches, job, **kwargs)
                record[label] = train_numbers(other, want)[0]
                record["passes"][label] = is_correct(
                    against(record[label], limits))
        record["seconds"] = time.perf_counter() - t
        harness.log(calibrate=record)
    sess.close()


def _slow_steps(durations):
    """Where a window's time went step by step: the median and the
    longest step, and every step over 1.5x the median with its place and
    its length (a stall shows here; a run that is slow throughout shows
    in the median)."""
    if not durations:
        return {}
    median = float(np.median(durations))
    return {"median_ms": 1000 * median,
            "max_ms": 1000 * float(np.max(durations)),
            "over_1.5x_median": [[i, round(1000 * d, 1)]
                                 for i, d in enumerate(durations)
                                 if d > 1.5 * median][:20]}


def run(ctx):
    import simple_tensorflow_tpu as stf
    from chipbench.reference import postln_transformer as ref

    config, job, args = ctx["config"], ctx["traffic"], ctx["args"]
    spec = config["reference"]["spec"]
    spans, clock = ctx["spans"], ctx["clock"]
    timings = {}
    t = time.perf_counter()

    model = build(config, job)
    timings["graph_build_s"] = time.perf_counter() - t
    batches = traffic_mod.train_batches(job, spec, args.seed)
    feeds = [{model[k]: v for k, v in b.items()} for b in batches]
    fetches = [model["train_op"], model["loss"]]
    n_check = job["check_steps"]

    t = time.perf_counter()
    mark = clock.mark()
    sess = stf.Session()
    variables = program_variables(config)
    init = load_weights(sess, variables, ref.init_params(spec, args.seed))
    timings["init_s"] = time.perf_counter() - t
    t = time.perf_counter()
    step_program = sess.plan(fetches, feeds=list(feeds[0])).compile()
    temp_bytes = int(step_program.memory_analysis().temp_size_in_bytes)
    timings["compile_or_cache_s"] = time.perf_counter() - t

    # -- the first steps, through the window's own call and feed ----------
    t = time.perf_counter()
    got = first_steps(sess, model, variables, feeds, job, init)
    del init
    timings["first_steps_s"] = time.perf_counter() - t
    timings["setup_compiles"] = clock.since(mark)

    counters0 = ctx["snapshot_counters"]()
    tracer = ctx["tracer"]
    mark = clock.mark()

    # -- the window ----------------------------------------------------------
    setup_s = time.perf_counter() - ctx["t_start"]
    t0 = time.perf_counter()
    tracer.arm()
    steps = n_check
    while time.perf_counter() - t0 < args.seconds:
        with spans.span("session_run"):
            _step(sess, fetches, feeds[steps % len(feeds)])
        steps += 1
        tracer.poll()
    tracer.stop()
    elapsed = time.perf_counter() - t0
    done = steps - n_check
    counters1 = ctx["snapshot_counters"]()
    window_compiles = clock.since(mark)
    peak_bytes = harness.memory_peak_bytes(ctx["devices"], temp_bytes)

    tokens_per_step = job["batch"] * job["seq_len"]
    flops_per_token = work.bert_train_flops_per_token(
        spec, job["seq_len"], job["masked_per_row"])
    facts = {"work": {}, "counters": {k: (counters0[k], counters1[k])
                                      for k in counters0}}
    if tracer.t1 is not None:
        inside = spans.within("session_run", tracer.t0, tracer.t1)
        facts["spans_in_trace"] = {"session_run": inside}
        # steps that ran inside the traced window, by their share of it
        traced_steps = len(inside) + sum(
            (min(b, tracer.t1) - max(a, tracer.t0)) / (b - a)
            for name, a, b in spans.records if name == "session_run"
            and (a < tracer.t0 < b or a < tracer.t1 < b))
        heads = spec["heads"]
        per_step = spec["layers"] * sum(
            np.asarray(work.flash_attention_work(
                job["batch"], heads, job["seq_len"], job["seq_len"],
                spec["hidden"] // heads, backward=backward), np.float64)
            for backward in (False, True))
        facts["work"] = {
            "model_flops": traced_steps * tokens_per_step * flops_per_token,
            "flash_attention": [float(x) for x in traced_steps * per_step],
            "traced_steps": traced_steps}

    # -- free the program, then follow the first steps in the reference -----
    sess.close()
    del sess, model, feeds, fetches, variables
    stf.reset_default_graph()
    gc.collect()
    t = time.perf_counter()
    want = ref.bert_train_reference(spec, args.seed, batches[:n_check], job)
    timings["reference_s"] = time.perf_counter() - t
    numbers, leaves = train_numbers(got, want)
    compared = against(numbers, ctx["limits"])

    return {
        "attempted": done, "failed": 0,
        "end_to_end": {"train_tokens_per_s": done * tokens_per_step / elapsed,
                       "setup_s": setup_s},
        "compared": compared, "facts": facts,
        "memory_peak_bytes": peak_bytes,
        "info": {"steps": done, "window_s": elapsed,
                 "step_times": _slow_steps(
                     [b - a for name, a, b in spans.records
                      if name == "session_run"]),
                 "real_tokens_per_s": done * float(
                     traffic_mod.row_lengths(job).sum()) / elapsed,
                 "losses": got["losses"],
                 "reference_losses": want["losses"], "numbers": numbers,
                 "leaves": leaves, "timings": timings,
                 "window_compiles": window_compiles,
                 "kernel_routing": harness.kernel_routing()},
    }
