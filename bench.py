#!/usr/bin/env python
"""Headline benchmarks: ResNet-50 and BERT-base training throughput on TPU.

Prints one JSON line per metric (ResNet first — the driver's primary —
then BERT): {"metric", "value", "unit", "vs_baseline", "mfu", ...}.
ResNet vs_baseline = images/sec/chip ÷ 210 (TF-1.0's published ResNet-50
P100 throughput — the reference's own hardware-era headline); BERT
vs_baseline is tokens/sec/chip ÷ 4000 (a P100-era BERT-base seq-512
pretraining rate, same vintage as the ResNet number). MFU is measured
against the chip's bf16 peak. BASELINE.json names both metrics.

Process contract: the parent stays off JAX (it imports only NumPy) and
runs each row in a child process, one at a time, so each child takes the
chip in turn. A child that finds no TPU fails; the parent prints the
rows that succeeded and exits non-zero when any row failed. The
virtual-mesh rows (resnet_dp, sharding_analysis, autoshard, embedding,
decode_tp) are CPU children by design and say so in their ``device``
field.
"""

import json
import os
import subprocess
import sys
import time

import numpy as np

def detect_peak_flops(device_kind, platform):
    """bf16 peak FLOP/s of the attached chip, from the repo's one table
    (utils/perf.CHIP_TABLE, keyed by device_kind). A device that is not
    in it — the CPU included — raises: no MFU against an assumed peak."""
    from simple_tensorflow_tpu.utils import perf

    if device_kind not in perf.CHIP_TABLE:
        raise ValueError(
            f"no published peak for device kind {device_kind!r} "
            f"(platform {platform!r}); see utils/perf.CHIP_TABLE")
    return perf.CHIP_TABLE[device_kind][0]


def emit(result):
    print(json.dumps(result))
    sys.stdout.flush()


def _roofline_info(sess, feed, sec_per_step, platform):
    """bytes-accessed + achieved HBM bandwidth of the session's training
    step (identifies whether a result is bandwidth- or compute-bound).
    Recompiles through the persistent cache."""
    from simple_tensorflow_tpu.utils import perf

    step = max((v for v in sess._cache.values() if v.has_device_stage),
               key=lambda s: len(s.device_ops))
    feeds = sess._normalize_feeds(feed)
    feed_args = {t.name: feeds[t] for t in step.feed_tensors}
    state = dict(sess._variable_store.values)
    compiled = step.jitted.lower(state, feed_args, sess._base_key,
                                 np.uint32(7)).compile()
    cost = perf.cost_of(compiled)
    peak_bw = perf.published_chip()[1]
    gbps = cost["bytes"] / sec_per_step / 1e9
    return {
        "bytes_accessed_gb": round(cost["bytes"] / 1e9, 2),
        "achieved_hbm_gbps": round(gbps, 1),
        "hbm_util": round(gbps * 1e9 / peak_bw, 3),
    }


def _predicted_info(m, sec_per_step, feed_tensors):
    """Static cost-model prediction next to the measured step."""
    from simple_tensorflow_tpu.client import timeline

    return {"predicted": timeline.predicted_vs_measured(
        [m["train_op"], m["loss"]], feeds=feed_tensors,
        measured_seconds=sec_per_step)}


def _monitoring_info():
    """Compact stf.monitoring snapshot for a bench row: executable-cache
    behavior + compile-time totals, so BENCH_*.json captures compile-time
    trends, not just steady-state step time. Counts are process-cumulative
    (a batch sweep's earlier candidates are included). Best-effort."""
    try:
        from simple_tensorflow_tpu.platform import monitoring

        exp = monitoring.export()

        def _cells(name):
            return exp.get(name, {}).get("cells", {})

        out = {
            "session_runs": _cells("/stf/session/runs").get("", 0),
            "cache_hits": _cells(
                "/stf/session/executable_cache/hits").get("", 0),
            "cache_misses": dict(_cells(
                "/stf/session/executable_cache/misses")),
            "fast_path_hits": _cells(
                "/stf/session/fast_path_hits").get("", 0),
            "fused_steps_amortized": _cells(
                "/stf/session/fused_steps_amortized").get("", 0),
            "loop_fusion_fallbacks": dict(_cells(
                "/stf/session/loop_fusion_fallbacks")),
        }
        compile_hist = _cells("/stf/session/jit_compile_seconds").get("")
        if compile_hist:
            out["jit_compiles"] = compile_hist["count"]
            out["jit_compile_seconds_total"] = round(compile_hist["sum"], 3)
        return {"monitoring": out}
    except Exception:
        return {}


def _measure_resnet(batch, image_size, steps, warmup, device_kind,
                    platform, recompute=None, s2d=None):
    import jax
    import jax.numpy as jnp

    import simple_tensorflow_tpu as stf
    from simple_tensorflow_tpu.models import resnet

    if recompute is None:
        # remat residual blocks: trades ~1.3x fwd FLOPs for the saved-
        # activation bytes — net win when HBM-bandwidth-bound (v5e)
        recompute = os.environ.get("BENCH_RESNET_RECOMPUTE", "0") == "1"
    if s2d is None:
        # MLPerf stem: space_to_depth conv0 (3-ch conv is the MXU's
        # worst case); flip on with BENCH_RESNET_S2D=1
        s2d = os.environ.get("BENCH_RESNET_S2D", "0") == "1"
    stf.reset_default_graph()
    m = resnet.resnet50_train_model(
        batch_size=batch, image_size=image_size,
        dtype=stf.bfloat16, learning_rate=0.1,
        recompute=recompute, conv0_space_to_depth=s2d)
    images, labels = resnet.synthetic_imagenet(batch, image_size,
                                               dtype=np.float32)
    # Stage the batch in HBM once: the bench measures the training step, not
    # host->device transfer bandwidth (real input pipelines double-buffer via
    # stf.data.prefetch_to_device).
    images_dev = jnp.asarray(images, dtype=stf.bfloat16.np_dtype)
    labels_dev = jnp.asarray(labels)
    feed = {m["images"]: images_dev, m["labels"]: labels_dev}

    sess = stf.Session()
    sess.run(stf.global_variables_initializer())

    t_compile0 = time.perf_counter()
    for _ in range(warmup):
        sess.run(m["train_op"], feed_dict=feed)
    _ = sess.run(m["loss"], feed_dict=feed)  # sync
    compile_s = time.perf_counter() - t_compile0

    t0 = time.perf_counter()
    for _ in range(steps):
        sess.run(m["train_op"], feed_dict=feed)
    loss = sess.run(m["loss"], feed_dict=feed)  # blocks on final state
    dt = time.perf_counter() - t0

    sec_per_step = dt / (steps + 1)
    images_per_sec = batch / sec_per_step
    train_flops_per_image = 3.0 * resnet.resnet_flops_per_image(
        50, image_size)
    achieved = images_per_sec * train_flops_per_image
    peak = detect_peak_flops(device_kind, platform)
    # roofline computed HERE, while this candidate's session is live, so
    # the sweep never retains a losing candidate's params/feed in HBM; the
    # extra lower+compile is a disk hit once the persistent cache is warm
    return {
        **_roofline_info(sess, feed, sec_per_step, platform),
        **_predicted_info(m, sec_per_step, [m["images"], m["labels"]]),
        **_monitoring_info(),
        "metric": "resnet50_images_per_sec_per_chip",
        "value": round(float(images_per_sec), 2),
        "unit": "images/sec/chip",
        "vs_baseline": round(float(images_per_sec) / 210.0, 3),
        "mfu": round(float(achieved / peak), 4),
        "batch": batch,
        "image_size": image_size,
        "sec_per_step": round(sec_per_step, 5),
        "warmup_plus_compile_s": round(compile_s, 1),
        "loss": round(float(np.asarray(loss)), 4),
        "device": str(jax.devices()[0]),
    }


def _sweep_batches(batches, measure):
    """Measure each batch size, keep the best throughput; OOM/failing
    candidates are recorded in "skipped" rather than failing the bench."""
    best, tried, errors, last_exc = None, [], [], None
    for batch in batches:
        try:
            r = measure(batch)
        except Exception as e:  # OOM at big batch: keep the smaller result
            errors.append(f"batch {batch}: {type(e).__name__}: "
                          f"{str(e)[:300]}")
            last_exc = e
            continue
        tried.append({"batch": r["batch"], "value": r["value"],
                      "mfu": r.get("mfu")})
        if best is None or r["value"] > best["value"]:
            best = r
    if best is None:
        raise RuntimeError(
            "all batch sizes failed: " + "; ".join(errors)) from last_exc
    if len(tried) > 1:
        best["batch_sweep"] = tried
    if errors:
        best["skipped"] = errors
    return best


def run_bench(platform, device_kind):
    """ResNet-50. On TPU, BENCH_BATCH may be a comma list (default
    "256,512"): each batch size is measured and the best throughput wins
    (batch is a free parameter of the images/sec metric; larger batches
    amortize bandwidth until HBM runs out — OOM candidates are skipped).

    After the batch sweep, the per-step byte levers — per-block remat
    (`recompute`) and the MLPerf space-to-depth stem (`s2d`) — are tried
    at the winning batch; the best variant is reported with its flags.
    Set BENCH_RESNET_VARIANTS=0 to pin the env-selected variant only.
    """
    batches = [int(b) for b in
               os.environ.get("BENCH_BATCH", "256,512").split(",") if b]
    image_size = int(os.environ.get("BENCH_IMAGE", "224"))
    steps = int(os.environ.get("BENCH_STEPS", "30"))
    warmup = int(os.environ.get("BENCH_WARMUP", "5"))
    try_variants = os.environ.get("BENCH_RESNET_VARIANTS", "1") == "1"

    if platform == "cpu":
        # CI / no-TPU fallback: shrink so the bench still completes.
        batches = [min(batches[0], 16)]
        image_size = min(image_size, 64)
        steps = min(steps, 5)
        warmup = 2
        try_variants = False

    # env flags pin the BASE variant; the sweep then only tries configs
    # that differ from it (no duplicate compiles, honest labels)
    env_rc = os.environ.get("BENCH_RESNET_RECOMPUTE", "0") == "1"
    env_s2d = os.environ.get("BENCH_RESNET_S2D", "0") == "1"

    def _vname(rc, s2):
        return {(False, False): "base", (True, False): "recompute",
                (False, True): "s2d", (True, True): "recompute+s2d"}[
            (rc, s2)]

    best = _sweep_batches(
        batches, lambda b: _measure_resnet(b, image_size, steps, warmup,
                                           device_kind, platform))
    if not try_variants:
        return best
    best["variant"] = _vname(env_rc, env_s2d)
    b = best["batch"]
    base_sweep = best.get("batch_sweep")
    base_skipped = best.get("skipped")
    variant_log = [{"variant": best["variant"], "value": best["value"]}]
    for rc, s2 in ((True, False), (False, True), (True, True)):
        if (rc, s2) == (env_rc, env_s2d):
            continue  # already measured as the base
        name = _vname(rc, s2)
        try:
            r = _measure_resnet(b, image_size, steps, warmup, device_kind,
                                platform, recompute=rc, s2d=s2)
        except Exception as e:  # OOM etc.: variant skipped, not fatal
            variant_log.append({"variant": name,
                                "error": f"{type(e).__name__}: "
                                         f"{str(e)[:200]}"})
            continue
        variant_log.append({"variant": name, "value": r["value"],
                            "mfu": r.get("mfu")})
        if r["value"] > best["value"]:
            r["variant"] = name
            best = r
    # carry the batch-sweep evidence (incl. OOM skips) whoever wins
    if base_sweep is not None:
        best["batch_sweep"] = base_sweep
    if base_skipped is not None:
        best["skipped"] = base_skipped
    best["variant_sweep"] = variant_log
    return best


def run_bench_bert(platform, device_kind):
    """BERT-base MLM+NSP pretraining step, seq 512, bf16 (BASELINE
    config 4's per-chip rate). BENCH_BERT_BATCH may be a comma list
    (default "24,32"); best tokens/sec wins, OOM candidates are skipped.
    On TPU, per-layer remat is then tried at the winning batch (remat
    frees activation HBM, which often buys a bigger viable batch — the
    remat run also retries batch+8); the best variant is reported."""
    batches = [int(b) for b in
               os.environ.get("BENCH_BERT_BATCH", "24,32").split(",") if b]
    if platform == "cpu":
        batches = batches[:1]
    env_rc = os.environ.get("BENCH_BERT_RECOMPUTE", "0") == "1"
    best = _sweep_batches(
        batches, lambda b: _measure_bert(b, platform, device_kind))
    if platform == "cpu" or os.environ.get("BENCH_BERT_VARIANTS",
                                           "1") != "1":
        return best
    best["variant"] = "recompute" if env_rc else "base"
    variant_log = [{"variant": best["variant"], "value": best["value"]}]
    if env_rc:
        trials = (("base", False, best["batch"]),
                  ("recompute_bigger_batch", True, best["batch"] + 8))
    else:
        trials = (("recompute", True, best["batch"]),
                  ("recompute_bigger_batch", True, best["batch"] + 8))
    for name, rc, b in trials:
        try:
            r = _measure_bert(b, platform, device_kind, recompute=rc)
        except Exception as e:
            variant_log.append({"variant": name,
                                "error": f"{type(e).__name__}: "
                                         f"{str(e)[:200]}"})
            continue
        variant_log.append({"variant": name, "value": r["value"],
                            "mfu": r.get("mfu")})
        if r["value"] > best["value"]:
            r["variant"] = name
            best = r
    best["variant_sweep"] = variant_log
    return best


def _measure_bert(batch, platform, device_kind, recompute=None):
    seq_len = int(os.environ.get("BENCH_BERT_SEQ", "512"))
    steps = int(os.environ.get("BENCH_STEPS", "20"))
    warmup = int(os.environ.get("BENCH_WARMUP", "3"))
    max_pred = max(1, int(seq_len * 0.15))

    import jax

    from simple_tensorflow_tpu.models import bert

    cfg = bert.BertConfig.base()
    if platform == "cpu":
        cfg = bert.BertConfig.tiny()
        batch, seq_len, max_pred, steps, warmup = 4, 64, 8, 3, 1
        cfg.max_position = seq_len

    import simple_tensorflow_tpu as stf

    stf.reset_default_graph()
    m = bert.bert_pretrain_model(
        batch_size=batch, seq_len=seq_len, max_predictions=max_pred,
        cfg=cfg, compute_dtype=stf.bfloat16, use_input_mask=True,
        # remat per layer (stf.recompute_grad): trades ~1.33x FLOPs for
        # activation HBM — enables larger batches when capacity-bound
        recompute=recompute if recompute is not None
        else os.environ.get("BENCH_BERT_RECOMPUTE", "0") == "1")
    batch_np = bert.synthetic_pretrain_batch(batch, seq_len, max_pred,
                                             vocab_size=cfg.vocab_size)
    batch_np["input_mask"] = np.ones((batch, seq_len), np.int32)
    import jax.numpy as jnp

    feed = {m[k]: jnp.asarray(v) for k, v in batch_np.items()}

    sess = stf.Session()
    sess.run(stf.global_variables_initializer())

    t_compile0 = time.perf_counter()
    for _ in range(warmup):
        sess.run(m["train_op"], feed_dict=feed)
    _ = sess.run(m["loss"], feed_dict=feed)  # sync + compile loss fetch
    compile_s = time.perf_counter() - t_compile0

    t0 = time.perf_counter()
    for _ in range(steps):
        sess.run(m["train_op"], feed_dict=feed)
    loss = sess.run(m["loss"], feed_dict=feed)
    dt = time.perf_counter() - t0

    sec_per_step = dt / (steps + 1)
    tokens_per_sec = batch * seq_len / sec_per_step
    train_flops_per_token = 3.0 * bert.bert_flops_per_token(cfg, seq_len)
    peak = detect_peak_flops(device_kind, platform)
    mfu = tokens_per_sec * train_flops_per_token / peak

    return {
        **_roofline_info(sess, feed, sec_per_step, platform),
        **_predicted_info(m, sec_per_step, list(feed.keys())),
        **_monitoring_info(),
        "metric": "bert_base_tokens_per_sec_per_chip",
        "value": round(float(tokens_per_sec), 1),
        "unit": "tokens/sec/chip",
        "vs_baseline": round(float(tokens_per_sec) / 4000.0, 3),
        "mfu": round(float(mfu), 4),
        "batch": batch,
        "seq_len": seq_len,
        "sec_per_step": round(sec_per_step, 5),
        "warmup_plus_compile_s": round(compile_s, 1),
        "loss": round(float(np.asarray(loss)), 4),
        "device": str(jax.devices()[0]),
    }


def _measure_mnist(platform, device_kind):
    """BASELINE config 1: MNIST softmax via tf.Session. The reference ran
    this single-device on CPU; comparator 10k examples/sec is a
    TF-1.0-era CPU softmax rate."""
    steps = int(os.environ.get("BENCH_STEPS", "30"))
    warmup = 3
    batch = 512

    import jax

    import simple_tensorflow_tpu as stf
    from simple_tensorflow_tpu.models import mnist

    stf.reset_default_graph()
    m = mnist.softmax_model(batch_size=batch, learning_rate=0.5)
    xv, _, onehot = mnist.synthetic_mnist(batch)
    import jax.numpy as jnp

    feed = {m["x"]: jnp.asarray(xv), m["y_"]: jnp.asarray(onehot)}
    sess = stf.Session()
    sess.run(stf.global_variables_initializer())
    t0 = time.perf_counter()
    for _ in range(warmup):
        sess.run(m["train_op"], feed_dict=feed)
    _ = sess.run(m["loss"], feed_dict=feed)
    compile_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    for _ in range(steps):
        sess.run(m["train_op"], feed_dict=feed)
    loss = sess.run(m["loss"], feed_dict=feed)
    dt = time.perf_counter() - t0
    sec_per_step = dt / (steps + 1)
    examples_per_sec = batch / sec_per_step
    return {
        **_monitoring_info(),
        "metric": "mnist_softmax_examples_per_sec",
        "value": round(float(examples_per_sec), 1),
        "unit": "examples/sec",
        "vs_baseline": round(float(examples_per_sec) / 10000.0, 3),
        "batch": batch,
        "sec_per_step": round(sec_per_step, 6),
        "warmup_plus_compile_s": round(compile_s, 1),
        "loss": round(float(np.asarray(loss)), 4),
        "device": str(jax.devices()[0]),
    }


def _measure_graph_opt(platform, device_kind):
    """Function-aware graph-optimizer micro-row (PR 1 tentpole): a
    conv-in-cond + conv/BN-in-scan-body model timed through the Session
    with the graph as built vs. after optimizer.optimize (layout into
    bodies, loop layout push, in-body CSE/fold, LICM). Emits both times
    and the speedup so the optimizer's win — which on an NCHW model is
    per-ITERATION transpose traffic — is pinned in the BENCH json. CPU
    fallback is fine; the delta is what matters."""
    steps = int(os.environ.get("BENCH_STEPS", "30"))
    warmup = 3

    import json as _json

    import jax

    import simple_tensorflow_tpu as stf
    from simple_tensorflow_tpu.framework import (cost_model, graph_io,
                                                 optimizer)

    rng = np.random.RandomState(0)
    n, c, hw, scan_steps = 8, 16, 32, 16

    def build():
        stf.reset_default_graph()
        x = stf.placeholder(stf.float32, [n, c, hw, hw], name="gx")
        w1 = stf.constant(rng.randn(3, 3, c, c).astype(np.float32) * 0.2,
                          name="gw1")
        w2 = stf.constant(rng.randn(3, 3, c, c).astype(np.float32) * 0.2,
                          name="gw2")
        scale = stf.constant(np.ones(c, np.float32))
        offset = stf.constant(np.zeros(c, np.float32))

        def branch_t():
            h = stf.nn.conv2d(x, w1, strides=[1, 1, 1, 1],
                              padding="SAME", data_format="NCHW")
            h, _, _ = stf.nn.fused_batch_norm(h, scale, offset,
                                              data_format="NCHW")
            return stf.nn.relu(h)

        def branch_f():
            return stf.nn.relu(stf.nn.conv2d(
                x, w2, strides=[1, 1, 1, 1], padding="SAME",
                data_format="NCHW"))

        h0 = stf.cond(stf.reduce_sum(x) > 0.0, branch_t, branch_f)
        dummy = stf.constant(np.zeros((scan_steps, 1), np.float32))

        def body(carry, _):
            h = stf.nn.conv2d(carry, w1, strides=[1, 1, 1, 1],
                              padding="SAME", data_format="NCHW")
            h, _, _ = stf.nn.fused_batch_norm(h, scale, offset,
                                              data_format="NCHW")
            return stf.nn.relu(h)

        out = stf.scan(body, dummy, initializer=h0)
        res = stf.reduce_mean(out[-1], name="graph_opt_res")
        return x, res

    rng = np.random.RandomState(0)
    xv = rng.randn(n, c, hw, hw).astype(np.float32)

    def timed(x, res):
        sess = stf.Session()
        for _ in range(warmup):
            sess.run(res, {x: xv})
        t0 = time.perf_counter()
        for _ in range(steps):
            val = sess.run(res, {x: xv})
        return (time.perf_counter() - t0) / steps, float(np.asarray(val))

    x, res = build()
    est_unopt = cost_model.estimate(res, feeds=[x])
    unopt_s, unopt_val = timed(x, res)
    gd = graph_io.graph_to_graphdef(stf.get_default_graph())
    opt = optimizer.optimize(gd, keep=[res.name, x.name])

    stf.reset_default_graph()
    graph_io.import_graph_def(_json.dumps(opt), name="")
    g = stf.get_default_graph()
    x2 = g.as_graph_element("gx:0", True, False)
    r2 = g.as_graph_element("graph_opt_res:0", True, False)
    est_opt = cost_model.estimate(r2, feeds=[x2])
    opt_s, opt_val = timed(x2, r2)

    return {
        **_monitoring_info(),
        "metric": "graph_opt_cond_scan_step_ms",
        "value": round(opt_s * 1e3, 3),
        "unit": "ms/step (optimized)",
        "vs_baseline": None,
        "unoptimized_ms": round(unopt_s * 1e3, 3),
        "speedup": round(unopt_s / max(opt_s, 1e-9), 3),
        "values_match": bool(abs(unopt_val - opt_val)
                             <= 1e-4 * max(1.0, abs(unopt_val))),
        "cost_model_bytes_unopt": round(est_unopt.bytes_accessed),
        "cost_model_bytes_opt": round(est_opt.bytes_accessed),
        "cost_model_bytes_ratio": round(
            est_opt.bytes_accessed / max(est_unopt.bytes_accessed, 1.0), 3),
        "scan_steps": scan_steps,
        "device": str(jax.devices()[0]),
    }


def _measure_analysis(platform, device_kind):
    """stf.analysis overhead row (ISSUE 3 satellite): per-plan cost of
    the verifier + variable-hazard detector relative to the rest of
    Session plan time (prune + optimize + lower staging), measured on
    the mnist convnet training plan via SOFTWARE_TRACE lifecycle spans
    and the /stf/analysis/plan_check_seconds monitoring sampler. The
    budget is <5% of plan time ("within_budget" in the row); jit
    compile is excluded from the denominator — against it the analysis
    cost would be unmeasurable noise."""
    import jax

    import simple_tensorflow_tpu as stf
    from simple_tensorflow_tpu.models import mnist
    from simple_tensorflow_tpu.platform import monitoring

    stf.reset_default_graph()
    m = mnist.convnet_model(batch_size=16)
    rng = np.random.RandomState(0)
    feed = {m["x"]: rng.rand(16, 28, 28, 1).astype(np.float32),
            m["y_"]: rng.randint(0, 10, 16).astype(np.int32),
            m["keep_prob"]: 0.9}
    sess = stf.Session(config=stf.ConfigProto(graph_analysis="warn"))
    sess.run(stf.global_variables_initializer())
    opts = stf.RunOptions(trace_level=stf.RunOptions.SOFTWARE_TRACE)
    md = stf.RunMetadata()
    sess.run([m["train_op"], m["loss"]], feed, options=opts,
             run_metadata=md)
    spans = {}
    for node in md.step_stats.get("nodes", []):
        phase = node["name"].split(":")[0]
        spans[phase] = spans.get(phase, 0.0) + node["dur_us"] / 1e6
    analysis_s = spans.get("analysis", 0.0)
    plan_s = sum(spans.get(k, 0.0)
                 for k in ("prune", "optimize", "lower", "analysis"))
    frac = analysis_s / plan_s if plan_s else 0.0
    exported = monitoring.export()

    def _cells(name):
        return exported.get(name, {}).get("cells", {})

    return {
        "metric": "analysis_overhead_frac",
        "value": round(frac, 4),
        "unit": "fraction of plan time (prune+optimize+lower+analysis)",
        "vs_baseline": None,
        "within_budget": bool(frac < 0.05),
        "analysis_ms": round(analysis_s * 1e3, 3),
        "plan_ms": round(plan_s * 1e3, 3),
        "n_plan_ops": md.step_stats.get("n_device_ops"),
        "monitoring": {
            "diagnostics": _cells("/stf/analysis/diagnostics"),
            "hazards": _cells("/stf/analysis/hazards"),
            "auto_control_deps": _cells("/stf/analysis/auto_control_deps"),
            # count/sum only: raw sampler cells carry an +inf bucket
            # edge, which json.dumps renders as the nonstandard
            # `Infinity` token no strict JSON parser accepts
            "plan_checks": {
                k: {"count": v["count"], "sum_s": round(v["sum"], 6)}
                for k, v in _cells(
                    "/stf/analysis/plan_check_seconds").items()},
        },
        "device": str(jax.devices()[0]),
    }


def _measure_sharding_analysis(platform, device_kind):
    """stf.analysis.sharding row (ISSUE 6): on the SAME model/mesh
    config as the resnet50_dp8_sharding_efficiency row (resnet50,
    bf16, batch 32, image 32, dp=8 virtual mesh), (1) the analyzer's
    predicted total collective bytes must land within 25% of the bytes
    harvested from the compiled executable's HLO collective
    instructions (utils/perf.collective_bytes_of), and (2) the
    analyzer's cost ON THE PLAN CRITICAL PATH must stay under 5% of
    Session plan time (prune + optimize + lower + analysis — the same
    budget discipline as the ISSUE 3 verifier+hazards row; jit compile
    excluded). The analysis itself runs on a worker thread overlapping
    the multi-second XLA compile (it is advisory — warnings, never an
    execution gate), so the blocking cost is the thread spawn; the full
    analyzer wall time is reported alongside (analyzer_wall_ms) and
    sampled on /stf/analysis/sharding_seconds."""
    import jax

    import simple_tensorflow_tpu as stf
    from simple_tensorflow_tpu import parallel
    from simple_tensorflow_tpu.models import resnet
    from simple_tensorflow_tpu.platform import monitoring

    devices = jax.devices()
    n_devices = 8
    assert len(devices) >= n_devices, (
        f"need {n_devices} virtual devices, have {len(devices)}")
    stf.reset_default_graph()
    mesh = parallel.Mesh({"dp": n_devices},
                         devices=devices[:n_devices])
    with mesh:
        m = resnet.resnet50_train_model(
            batch_size=32, image_size=32, dtype=stf.bfloat16,
            learning_rate=0.1)
        parallel.shard_feed(m["images"], "dp")
        parallel.shard_feed(m["labels"], "dp")
        xv, yv = resnet.synthetic_imagenet(32, 32, dtype=np.float32)
        feed = {m["images"]: xv.astype(stf.bfloat16.np_dtype),
                m["labels"]: yv}
        sess = stf.Session()
        sess.run(stf.global_variables_initializer())
        opts = stf.RunOptions(trace_level=stf.RunOptions.SOFTWARE_TRACE)
        md = stf.RunMetadata()
        sess.run([m["train_op"], m["loss"]], feed, options=opts,
                 run_metadata=md)
    steps = [s for s in sess._cache.values()
             if s.join_sharding() is not None]
    assert steps, ("no plan produced a sharding report — check the "
                   "stf log for sharding/analysis-failed notes")
    step = steps[-1]
    rep = step.sharding_report
    predicted = rep.total_collective_bytes()
    harvested = md.cost_graph.get("collective_bytes", {})
    harvested_total = float(harvested.get("total", 0.0))
    ratio = predicted / harvested_total if harvested_total else None
    spans = {}
    for node in md.step_stats.get("nodes", []):
        phase = node["name"].split(":")[0]
        spans[phase] = spans.get(phase, 0.0) + node["dur_us"] / 1e6
    plan_s = sum(spans.get(k, 0.0)
                 for k in ("prune", "optimize", "lower", "analysis"))
    blocking_s = step.sharding_sync_seconds
    frac = blocking_s / plan_s if plan_s else 0.0
    exported = monitoring.export()

    def _cells(name):
        return exported.get(name, {}).get("cells", {})

    return {
        "metric": "sharding_analysis_overhead_frac",
        "value": round(frac, 4),
        "unit": ("fraction of plan time (prune+optimize+lower+"
                 "analysis) spent blocking on sharding analysis"),
        "vs_baseline": None,
        "within_budget": bool(frac < 0.05),
        "blocking_ms": round(blocking_s * 1e3, 3),
        "analyzer_wall_ms": round(rep.analysis_seconds * 1e3, 3),
        "overlapped_with": "lowering + jit compile (worker thread)",
        "plan_ms": round(plan_s * 1e3, 3),
        "predicted_collective_bytes": round(predicted),
        "harvested_collective_bytes": round(harvested_total),
        "predicted_over_harvested": (round(ratio, 4)
                                     if ratio is not None else None),
        "within_25pct": (bool(abs(ratio - 1.0) <= 0.25)
                         if ratio is not None else None),
        "predicted_by_kind": {k: round(v) for k, v in
                              rep.bytes_by_kind().items()},
        "harvested_by_kind": {k: round(v) for k, v in
                              harvested.items() if k != "total"},
        "n_collective_edges": len(rep.collective_edges()),
        "monitoring": {
            "sharding_collectives": _cells(
                "/stf/analysis/sharding_collectives"),
            "sharding_collective_bytes": _cells(
                "/stf/analysis/sharding_collective_bytes"),
            "sharding_seconds": {
                k: {"count": v["count"], "sum_s": round(v["sum"], 6)}
                for k, v in _cells(
                    "/stf/analysis/sharding_seconds").items()},
        },
        "device": str(jax.devices()[0]),
    }


def _measure_loop_fusion(platform, device_kind):
    """Loop-fusion amortization row (ISSUE 4 tentpole): the BERT-base
    small-step training loop — the BENCH_r05 regime whose
    measured_over_predicted hit ~108x because per-step host work (feed
    staging, dispatch, blocking loss fetch) dwarfed the tiny device
    program — swept over fused window sizes N in {1, 8, 64}.

    N=1 is the canonical host-driven loop: pull a numpy batch from the
    input pipeline, Session.run([train_op, loss]), materialize the loss
    — one full host round-trip per step. N>1 is the device-resident
    loop: stf.data superbatches N batches and stages them in device
    memory on the prefetch thread, Session.run_steps compiles N steps
    into ONE lax.scan program (variables in the donated carry, per-step
    RNG split on-device), and all N per-step losses come back in a
    single device_get. Both paths consume the same logical batch stream
    and surface the same per-step losses.

    Reported per N: sec_per_step and measured_over_predicted against
    the SAME static per-step prediction (host-dispatch-floored roofline,
    framework/cost_model.py), so the improvement factor is purely the
    amortization. The CPU fallback shrinks BERT until the step is
    dispatch-dominated (1 layer, hidden 16, batch 1, seq 8 — the
    small-step extreme); on compute-bound configs XLA:CPU executes scan
    bodies no faster than standalone steps, so fusion has nothing to
    amortize and N=1 wins — the sweep records whichever is true."""
    steps_budget = int(os.environ.get("BENCH_FUSION_STEPS", "192"))

    import jax

    import simple_tensorflow_tpu as stf
    from simple_tensorflow_tpu.data.dataset import Dataset
    from simple_tensorflow_tpu.framework import cost_model
    from simple_tensorflow_tpu.models import bert

    cfg = bert.BertConfig.base()
    batch, seq_len, max_pred = 24, 512, 76
    compute_dtype = stf.bfloat16
    if platform == "cpu":
        cfg = bert.BertConfig(
            vocab_size=99, hidden_size=16, num_layers=1, num_heads=2,
            intermediate_size=32, max_position=8, hidden_dropout=0.0,
            attention_dropout=0.0)
        batch, seq_len, max_pred = 1, 8, 1
        # f32 on CPU: bf16 there is convert-kernel emulation, which
        # inflates the device floor and would measure dtype emulation
        # instead of dispatch amortization
        compute_dtype = stf.float32

    stf.reset_default_graph()
    m = bert.bert_pretrain_model(
        batch_size=batch, seq_len=seq_len, max_predictions=max_pred,
        cfg=cfg, compute_dtype=compute_dtype, use_input_mask=True)
    batch_np = bert.synthetic_pretrain_batch(batch, seq_len, max_pred,
                                             vocab_size=cfg.vocab_size)
    batch_np["input_mask"] = np.ones((batch, seq_len), np.int32)

    sess = stf.Session()
    sess.run(stf.global_variables_initializer())
    fetch = [m["train_op"], m["loss"]]
    feed_tensors = [m[k] for k in batch_np]
    est = cost_model.estimate(fetch, feeds=feed_tensors)

    def batch_stream():
        while True:
            yield dict(batch_np)

    def measure_n(n):
        """Median sec_per_step of the canonical loop at window size n:
        a per-step loop (n=1: pull numpy batch, run [train_op, loss],
        materialize the loss) vs the device-resident loop (n>1:
        prefetch_to_device superbatches feed Session.run_steps; all n
        per-step losses come back in one device_get). Median of 3 timed
        rounds — the per-step host overhead being measured is exactly
        the jittery part."""
        rounds = []
        if n == 1:
            it = iter(batch_stream())
            feed = {m[k]: v for k, v in next(it).items()}
            sess.run(fetch, feed_dict=feed)
            timed = max(8, min(steps_budget, 64))
            for _ in range(3):
                t0 = time.perf_counter()
                for _ in range(timed):
                    feed = {m[k]: v for k, v in next(it).items()}
                    _, loss = sess.run(fetch, feed_dict=feed)
                    float(np.asarray(loss))  # per-step host round-trip
                rounds.append((time.perf_counter() - t0) / timed)
        else:
            ds = Dataset.from_generator(batch_stream).prefetch_to_device(
                buffer_size=2, superbatch=n)
            it = iter(ds)
            sb = {m[k]: v for k, v in next(it).items()}
            out = sess.run_steps(fetch, n=n, stacked_feeds=sb,
                                 output_mode="stacked")
            np.asarray(out[1])
            windows = max(1, steps_budget // n)
            for _ in range(3):
                t0 = time.perf_counter()
                for _ in range(windows):
                    sb = {m[k]: v for k, v in next(it).items()}
                    out = sess.run_steps(fetch, n=n, stacked_feeds=sb,
                                         output_mode="stacked")
                    np.asarray(out[1])  # all n losses, ONE device_get
                rounds.append((time.perf_counter() - t0) / (windows * n))
        return float(np.median(rounds)), rounds

    sweep = []
    base_mop = None
    for n in (1, 8, 64):
        sec_per_step, rounds = measure_n(n)
        pred = cost_model.predicted_vs_measured(
            fetch, feeds=feed_tensors, measured_seconds=sec_per_step,
            est=est)
        row = {"n": n, "sec_per_step": round(sec_per_step, 6),
               "rounds_sec_per_step": [round(r, 6) for r in rounds],
               "measured_over_predicted": pred.get(
                   "measured_over_predicted")}
        if base_mop is None:
            base_mop = row["measured_over_predicted"]
        sweep.append(row)
    final_mop = sweep[-1]["measured_over_predicted"]
    improvement = (round(base_mop / final_mop, 2)
                   if base_mop and final_mop else None)
    return {
        **_monitoring_info(),
        "metric": "loop_fusion_bert_amortization_n64_vs_n1",
        "value": improvement,
        "unit": "x (measured_over_predicted improvement)",
        "vs_baseline": None,
        "amortization_sweep": sweep,
        "predicted_sec_per_step": cost_model.predicted_vs_measured(
            fetch, feeds=feed_tensors, est=est).get(
                "predicted_sec_per_step"),
        "batch": batch,
        "seq_len": seq_len,
        "num_layers": cfg.num_layers,
        "hidden_size": cfg.hidden_size,
        "device": str(jax.devices()[0]),
    }


def _measure_numerics(platform, device_kind):
    """Numerics-health-plane overhead row (ISSUE 17 satellite): the same
    BERT fused-loop config as the loop_fusion row, N=64 windows, timed
    with the plane OFF (plain Session) and ON
    (ConfigProto(numerics="metrics")). ON auto-taps the gradients,
    optimizer updates and loss and threads the packed [64, 4]
    NumericSummary health tensor through the lax.scan carry — the whole
    point of the design is that the window does NOT split, so the cost
    should be a few extra device reductions amortized over 64 steps.
    The row's value is the percent overhead (target <3% at N=64); the
    monitoring snapshot rides along so the /stf/train/* families
    (health_steps, nonfinite_events, grad_norm, update_ratio) are
    visible in the emitted line."""
    steps_budget = int(os.environ.get("BENCH_FUSION_STEPS", "192"))
    n = 64

    import jax

    import simple_tensorflow_tpu as stf
    from simple_tensorflow_tpu.data.dataset import Dataset
    from simple_tensorflow_tpu.models import bert

    cfg = bert.BertConfig.base()
    batch, seq_len, max_pred = 24, 512, 76
    compute_dtype = stf.bfloat16
    if platform == "cpu":
        cfg = bert.BertConfig(
            vocab_size=99, hidden_size=16, num_layers=1, num_heads=2,
            intermediate_size=32, max_position=8, hidden_dropout=0.0,
            attention_dropout=0.0)
        batch, seq_len, max_pred = 1, 8, 1
        compute_dtype = stf.float32

    stf.reset_default_graph()
    m = bert.bert_pretrain_model(
        batch_size=batch, seq_len=seq_len, max_predictions=max_pred,
        cfg=cfg, compute_dtype=compute_dtype, use_input_mask=True)
    batch_np = bert.synthetic_pretrain_batch(batch, seq_len, max_pred,
                                             vocab_size=cfg.vocab_size)
    batch_np["input_mask"] = np.ones((batch, seq_len), np.int32)
    fetch = [m["train_op"], m["loss"]]

    def batch_stream():
        while True:
            yield dict(batch_np)

    def measure(sess):
        """Median sec_per_step over 3 timed rounds of N=64 fused
        windows — identical loop shape to the loop_fusion row so OFF
        here reproduces that row's fused regime."""
        sess.run(stf.global_variables_initializer())
        ds = Dataset.from_generator(batch_stream).prefetch_to_device(
            buffer_size=2, superbatch=n)
        it = iter(ds)
        sb = {m[k]: v for k, v in next(it).items()}
        out = sess.run_steps(fetch, n=n, stacked_feeds=sb,
                             output_mode="stacked")
        np.asarray(out[1])
        windows = max(1, steps_budget // n)
        rounds = []
        for _ in range(3):
            t0 = time.perf_counter()
            for _ in range(windows):
                sb = {m[k]: v for k, v in next(it).items()}
                out = sess.run_steps(fetch, n=n, stacked_feeds=sb,
                                     output_mode="stacked")
                np.asarray(out[1])
            rounds.append((time.perf_counter() - t0) / (windows * n))
        return float(np.median(rounds)), rounds

    off_sec, off_rounds = measure(stf.Session())
    on_sec, on_rounds = measure(stf.Session(
        config=stf.ConfigProto(numerics="metrics")))
    overhead_pct = round((on_sec / off_sec - 1.0) * 100.0, 2)

    from simple_tensorflow_tpu.debug import numerics as _numerics
    plane = _numerics.get_plane().info()
    return {
        **_monitoring_info(),  # after ON: /stf/train/* families populated
        "metric": "numerics_plane_overhead_pct_fused_n64",
        "value": overhead_pct,
        "unit": "% overhead (numerics metrics plane ON vs OFF, "
                "fused N=64)",
        "vs_baseline": None,
        "n": n,
        "off_sec_per_step": round(off_sec, 6),
        "on_sec_per_step": round(on_sec, 6),
        "off_rounds_sec_per_step": [round(r, 6) for r in off_rounds],
        "on_rounds_sec_per_step": [round(r, 6) for r in on_rounds],
        "health_steps_observed": plane.get("steps_observed"),
        "health_taps": len(plane.get("taps", ())),
        "batch": batch,
        "seq_len": seq_len,
        "num_layers": cfg.num_layers,
        "hidden_size": cfg.hidden_size,
        "device": str(jax.devices()[0]),
    }


def _measure_input_pipeline(platform, device_kind):
    """Input-pipeline engine row (ISSUE 5 tentpole): records/sec over 8
    synthetic TFRecord shards — the SEED sequential chain (single-thread
    nested generators, per-record Example parse before batching: the
    idiom the seed's pipelines used) vs the parallel engine (sharded C++
    chunk reads with num_parallel_reads=AUTOTUNE, one C++ batch-parse
    call per batch, autotuned prefetch). Also times a tiny
    pipeline-BOUND train step fed from each chain. Interleaved median of
    3 rounds (CPU wall-clock swings ~2x run to run); shards stay small
    per the tier-1 timing constraints."""
    import tempfile

    import jax

    import simple_tensorflow_tpu as stf
    import simple_tensorflow_tpu.ops.parsing_ops as po
    from simple_tensorflow_tpu import data as stf_data
    from simple_tensorflow_tpu.data import AUTOTUNE
    from simple_tensorflow_tpu.lib.example import make_example
    from simple_tensorflow_tpu.lib.io import tf_record

    shards = 8
    recs = int(os.environ.get("BENCH_PIPELINE_RECORDS", "1200"))
    feat = 64
    batch = 32
    tmp = tempfile.mkdtemp(prefix="stf_bench_pipeline_")
    rng = np.random.RandomState(0)
    files = []
    for s in range(shards):
        p = os.path.join(tmp, f"shard{s}.tfrecord")
        with tf_record.TFRecordWriter(p) as w:
            for i in range(recs):
                w.write(make_example(
                    x=[float(v) for v in rng.randn(feat)],
                    y=[s * recs + i]).SerializeToString())
        files.append(p)
    spec = {"x": po.FixedLenFeature([feat], stf.float32),
            "y": po.FixedLenFeature([1], stf.int64)}

    def seq_chain():
        # the seed idiom: sequential shard reads, parse each record as
        # it arrives (one parse call per proto), then batch
        return (stf_data.TFRecordDataset(files)
                .parse_example(spec).batch(batch))

    def par_chain():
        # the engine: parallel sharded reads, batch THEN one C++ parse
        # call per batch, autotuned prefetch decoupling
        return (stf_data.TFRecordDataset(files,
                                         num_parallel_reads=AUTOTUNE)
                .batch(batch).parse_example(spec).prefetch(AUTOTUNE))

    def records_per_sec(mk):
        n = 0
        t0 = time.perf_counter()
        for b in mk():
            n += len(b["y"])
        return n / (time.perf_counter() - t0)

    import shutil

    try:
        seq_rates, par_rates = [], []
        for _ in range(3):  # interleaved so box noise hits both arms
            seq_rates.append(records_per_sec(seq_chain))
            par_rates.append(records_per_sec(par_chain))
        seq_med = float(np.median(seq_rates))
        par_med = float(np.median(par_rates))

        # pipeline-BOUND train-step time: a step cheap enough that input
        # dominates; the engine's win shows up as wall-clock steps/sec
        def steps_per_sec(mk, n_steps=60):
            stf.reset_default_graph()
            x = stf.placeholder(stf.float32, [batch, feat])
            w = stf.Variable(np.zeros((feat, 1), np.float32))
            loss = stf.reduce_mean(stf.square(stf.matmul(x, w)))
            train = stf.train.GradientDescentOptimizer(0.01).minimize(loss)
            with stf.Session() as sess:
                sess.run(stf.global_variables_initializer())
                it = iter(mk())
                b = next(it)
                sess.run(train, {x: b["x"]})  # compile outside the clock
                t0 = time.perf_counter()
                done = 0
                for b in it:
                    sess.run(train, {x: b["x"]})
                    done += 1
                    if done >= n_steps:
                        break
                dt = time.perf_counter() - t0
                if hasattr(it, "close"):
                    it.close()
            return done / dt

        seq_steps = steps_per_sec(seq_chain)
        par_steps = steps_per_sec(par_chain)
    finally:
        shutil.rmtree(tmp, ignore_errors=True)
    return {
        **_monitoring_info(),
        "metric": "input_pipeline_records_per_sec",
        "value": round(par_med, 1),
        "unit": "records/sec",
        "vs_baseline": None,
        "seq_records_per_sec": round(seq_med, 1),
        "speedup": round(par_med / max(seq_med, 1e-9), 2),
        "seq_rates": [round(r, 1) for r in seq_rates],
        "par_rates": [round(r, 1) for r in par_rates],
        "pipeline_bound_steps_per_sec_seq": round(seq_steps, 2),
        "pipeline_bound_steps_per_sec_par": round(par_steps, 2),
        "train_step_speedup": round(par_steps / max(seq_steps, 1e-9), 2),
        "shards": shards,
        "records_per_shard": recs,
        "batch": batch,
        "device": str(jax.devices()[0]),
    }


def _measure_serving(platform, device_kind):
    """Serving row (ISSUE 7 tentpole): QPS + p50/p99 latency under
    synthetic concurrent CLOSED-LOOP load (each client issues its next
    request when the previous response materializes), continuous
    batching (stf.serving.ModelServer: AOT-per-bucket, coalescing
    batcher) vs the batch=1 sequential baseline (the pre-PR idiom: one
    Session.run per request, 16 client threads contending for the
    session). Interleaved median of BENCH_SERVING_ROUNDS (default 5)
    rounds (CPU wall-clock swings ~2x run to run). The acceptance bar
    is batched >= 3x baseline QPS at >= 16 clients."""
    import shutil
    import tempfile
    import threading

    import jax

    import simple_tensorflow_tpu as stf
    from simple_tensorflow_tpu import saved_model as sm
    from simple_tensorflow_tpu import serving
    from simple_tensorflow_tpu.platform import monitoring

    in_dim, hidden, classes = 128, 256, 10
    n_clients = int(os.environ.get("BENCH_SERVING_CLIENTS", "16"))
    measure_s = float(os.environ.get("BENCH_SERVING_SECONDS", "2.0"))
    rounds = int(os.environ.get("BENCH_SERVING_ROUNDS", "5"))
    max_batch = 16
    # 0.5 ms close timeout: with 16 closed-loop clients batches close
    # full on max_batch_size; the short timeout only bounds the tail
    # wait when the queue momentarily drains (swept 0.2-2 ms: 0.5 best)
    batch_timeout_ms = 0.5

    rng = np.random.RandomState(0)
    x = stf.placeholder(stf.float32, [None, in_dim], name="x")
    w1 = stf.Variable(stf.constant(
        (rng.randn(in_dim, hidden) * 0.05).astype(np.float32)), name="w1")
    b1 = stf.Variable(stf.constant(np.zeros(hidden, np.float32)),
                      name="b1")
    w2 = stf.Variable(stf.constant(
        (rng.randn(hidden, classes) * 0.05).astype(np.float32)),
        name="w2")
    b2 = stf.Variable(stf.constant(np.zeros(classes, np.float32)),
                      name="b2")
    h = stf.tanh(stf.add(stf.matmul(x, w1), b1))
    probs = stf.nn.softmax(stf.add(stf.matmul(h, w2), b2), name="probs")
    tmp = tempfile.mkdtemp(prefix="stf_bench_serving_")
    export_dir = os.path.join(tmp, "model")
    with stf.Session() as sess:
        sess.run(stf.global_variables_initializer())
        sm.simple_save(sess, export_dir, inputs={"x": x},
                       outputs={"probs": probs})
    stf.reset_default_graph()
    examples = rng.randn(64, in_dim).astype(np.float32)

    def closed_loop(run_once, seconds):
        """n_clients closed-loop threads for ~seconds; returns
        (qps, p50_ms, p99_ms) over completed requests."""
        counts = [0] * n_clients
        lats: list = [[] for _ in range(n_clients)]
        start_gate = threading.Barrier(n_clients + 1)
        stop_at = [0.0]

        def client(i):
            start_gate.wait()
            j = i
            while time.perf_counter() < stop_at[0]:
                t0 = time.perf_counter()
                run_once(examples[j % len(examples)])
                lats[i].append(time.perf_counter() - t0)
                counts[i] += 1
                j += n_clients
        threads = [threading.Thread(target=client, args=(i,), daemon=True)
                   for i in range(n_clients)]
        for t in threads:
            t.start()
        t0 = time.perf_counter()
        stop_at[0] = t0 + seconds
        start_gate.wait()
        for t in threads:
            t.join()
        wall = time.perf_counter() - t0
        all_lats = np.array(sorted(sum(lats, [])))
        total = int(sum(counts))
        if total == 0:
            return 0.0, 0.0, 0.0
        return (total / wall,
                float(np.percentile(all_lats, 50) * 1e3),
                float(np.percentile(all_lats, 99) * 1e3))

    try:
        # batched arm: continuous batcher, AOT-warmed buckets
        server = serving.ModelServer(policy=serving.BatchingPolicy(
            max_batch_size=max_batch, batch_timeout_ms=batch_timeout_ms,
            max_queue_depth=4 * max_batch))
        server.load(export_dir, name="bench")

        def run_batched(ex):
            server.predict({"x": ex}).result(timeout=120)

        # baseline arm: one batch=1 Session.run per request — the only
        # serving story the repo had before this PR
        base_graph = stf.Graph()
        with base_graph.as_default():
            base_sess = stf.Session(graph=base_graph)
            meta = sm.loader.load(base_sess, [sm.tag_constants.SERVING],
                                  export_dir)
        sig = meta["signature_def"]["serving_default"]
        xn = sig["inputs"]["x"]["name"]
        yn = sig["outputs"]["probs"]["name"]

        def run_base(ex):
            base_sess.run(yn, {xn: ex[None, :]})

        # warmup both arms outside the clock (compiles: baseline's
        # batch-1 program; server buckets were AOT-compiled at load)
        run_base(examples[0])
        for _ in range(4):
            run_batched(examples[0])

        base_rounds, batched_rounds = [], []
        for _ in range(rounds):  # interleaved so box noise hits both
            base_rounds.append(closed_loop(run_base, measure_s))
            batched_rounds.append(closed_loop(run_batched, measure_s))
        base_qps = float(np.median([r[0] for r in base_rounds]))
        batched_qps = float(np.median([r[0] for r in batched_rounds]))
        base_med = min(base_rounds, key=lambda r: abs(r[0] - base_qps))
        batched_med = min(batched_rounds,
                          key=lambda r: abs(r[0] - batched_qps))
        fill = monitoring.export().get("/stf/serving/batch_fill", {})
        cell = (fill.get("cells") or {}).get("bench/serving_default", {})
        fill_mean = (cell.get("sum", 0.0) / cell["count"]) \
            if cell.get("count") else None
        size_m = monitoring.export().get("/stf/serving/batch_size", {})
        scell = (size_m.get("cells") or {}).get("bench/serving_default",
                                                {})
        size_mean = (scell.get("sum", 0.0) / scell["count"]) \
            if scell.get("count") else None
        base_sess.close()
        server.close()
    finally:
        shutil.rmtree(tmp, ignore_errors=True)
    return {
        **_monitoring_info(),
        "metric": "serving_qps_speedup_batched_vs_batch1",
        "value": round(batched_qps / max(base_qps, 1e-9), 2),
        "unit": f"x (QPS, {n_clients} concurrent closed-loop clients)",
        "vs_baseline": None,
        "qps_batched": round(batched_qps, 1),
        "qps_batch1": round(base_qps, 1),
        "p50_ms_batched": round(batched_med[1], 2),
        "p99_ms_batched": round(batched_med[2], 2),
        "p50_ms_batch1": round(base_med[1], 2),
        "p99_ms_batch1": round(base_med[2], 2),
        "batch_fill_mean": round(fill_mean, 3) if fill_mean else None,
        "batch_size_mean": round(size_mean, 2) if size_mean else None,
        "qps_batched_rounds": [round(r[0], 1) for r in batched_rounds],
        "qps_batch1_rounds": [round(r[0], 1) for r in base_rounds],
        "n_clients": n_clients,
        "max_batch_size": max_batch,
        "batch_timeout_ms": batch_timeout_ms,
        "measure_s": measure_s,
        "model": f"mlp {in_dim}x{hidden}x{classes} f32",
        "device": str(jax.devices()[0]),
    }


def _measure_telemetry(platform, device_kind):
    """Telemetry row (ISSUE 8 satellite): serving QPS and train-loop
    step time with the WHOLE telemetry plane ON (flight recorder +
    per-request span tracing + HTTP exporter being scraped) vs OFF.

    Two measurements, because this box cannot certify a 3% bound with
    wall clocks alone (consecutive IDENTICAL serving rounds show a
    ~20-25% QPS coefficient of variation — measured, reported in the
    row):

    - A/B medians of PAIRED ABBA rounds (``ab_*`` fields):
      informational; the honest wall-clock numbers with their noise.
    - The PINNED overhead (``value``): measured per-event costs
      (record / emit_span / a /metrics render, microbenched in this
      process) x measured event rates (counter deltas during the ON
      rounds), conservatively assuming every telemetry microsecond
      serializes against the workload. Both factors are real
      measurements; no wall-clock subtraction, so no noise floor.

    The acceptance bar pins the WORST of the serving and train
    accounted fractions < 3%."""
    import shutil
    import tempfile
    import threading
    import urllib.request

    import jax

    import simple_tensorflow_tpu as stf
    from simple_tensorflow_tpu import saved_model as sm
    from simple_tensorflow_tpu import serving, telemetry
    from simple_tensorflow_tpu.platform import monitoring
    from simple_tensorflow_tpu.telemetry import tracing as ttracing

    rounds = int(os.environ.get("BENCH_TELEMETRY_ROUNDS", "6"))
    serve_s = float(os.environ.get("BENCH_TELEMETRY_SECONDS", "1.5"))
    n_clients = 8
    train_steps = int(os.environ.get("BENCH_TELEMETRY_TRAIN_STEPS",
                                     "400"))
    in_dim, hidden, classes = 128, 256, 10
    rng = np.random.RandomState(0)

    # -- serving arm ---------------------------------------------------------
    x = stf.placeholder(stf.float32, [None, in_dim], name="x")
    w1 = stf.Variable(stf.constant(
        (rng.randn(in_dim, hidden) * 0.05).astype(np.float32)), name="w1")
    w2 = stf.Variable(stf.constant(
        (rng.randn(hidden, classes) * 0.05).astype(np.float32)),
        name="w2")
    probs = stf.nn.softmax(stf.matmul(stf.tanh(stf.matmul(x, w1)), w2),
                           name="probs")
    tmp = tempfile.mkdtemp(prefix="stf_bench_telemetry_")
    export_dir = os.path.join(tmp, "model")
    with stf.Session() as sess:
        sess.run(stf.global_variables_initializer())
        sm.simple_save(sess, export_dir, inputs={"x": x},
                       outputs={"probs": probs})
    stf.reset_default_graph()
    examples = rng.randn(64, in_dim).astype(np.float32)

    def serving_round(server, seconds):
        counts = [0] * n_clients
        gate = threading.Barrier(n_clients + 1)
        stop_at = [0.0]

        def client(i):
            gate.wait()
            j = i
            while time.perf_counter() < stop_at[0]:
                server.predict({"x": examples[j % 64]}).result(
                    timeout=120)
                counts[i] += 1
                j += n_clients
        threads = [threading.Thread(target=client, args=(i,),
                                    daemon=True)
                   for i in range(n_clients)]
        for t in threads:
            t.start()
        t0 = time.perf_counter()
        stop_at[0] = t0 + seconds
        gate.wait()
        for t in threads:
            t.join()
        return sum(counts) / (time.perf_counter() - t0)

    # -- train arm -----------------------------------------------------------
    g = stf.Graph()
    with g.as_default():
        xt = stf.placeholder(stf.float32, [32, in_dim], name="xt")
        wt = stf.get_variable(
            "wt", [in_dim, in_dim],
            initializer=stf.random_normal_initializer(stddev=0.05))
        loss = stf.reduce_sum(stf.matmul(xt, wt))
        opt = stf.train.GradientDescentOptimizer(1e-4).minimize(loss)
        train_sess = stf.Session(graph=g)
        with g.as_default():
            train_sess.run(stf.global_variables_initializer())
    feed = {xt: np.ones((32, in_dim), np.float32)}

    def train_round(steps):
        train_sess.run(opt, feed)  # warm (compile outside the clock)
        t0 = time.perf_counter()
        for _ in range(steps):
            train_sess.run(opt, feed)
        return (time.perf_counter() - t0) / steps

    rec = telemetry.get_recorder()

    def set_plane(on):
        rec.set_enabled(on)
        ttracing.set_enabled(on)

    scrape_errors = []
    try:
        server = serving.ModelServer(policy=serving.BatchingPolicy(
            max_batch_size=16, batch_timeout_ms=0.5,
            max_queue_depth=64))
        server.load(export_dir, name="bench_telemetry")
        for _ in range(4):  # warm every arm outside the clock
            server.predict({"x": examples[0]}).result(timeout=120)
        train_round(8)

        tsrv = telemetry.start(port=0)
        scrape_stop = threading.Event()
        scrapes = [0]

        def scraper():
            # a live Prometheus scraper is part of the ON cost (a
            # production scrape interval is 10-60 s; 250 ms here makes
            # the exporter cost VISIBLE at bench timescales, it does
            # not model a real scraper's duty cycle)
            while not scrape_stop.is_set():
                try:
                    with urllib.request.urlopen(
                            tsrv.url + "/metrics", timeout=10) as r:
                        r.read()
                    scrapes[0] += 1
                except Exception as e:  # noqa: BLE001
                    scrape_errors.append(repr(e))
                scrape_stop.wait(0.25)

        def measure_arm(on):
            if on:
                set_plane(True)
                scrape_stop.clear()
                th = threading.Thread(target=scraper, daemon=True,
                                      name="stf_bench_scraper")
                th.start()
            else:
                set_plane(False)
                th = None
            q = serving_round(server, serve_s)
            s = train_round(train_steps)
            if th is not None:
                scrape_stop.set()
                th.join(10)
            return q, s

        def _flight_counts():
            snap = monitoring.export().get(
                "/stf/telemetry/flight_events", {})
            cells = snap.get("cells") or {}
            return sum(cells.values()), cells.get("span", 0)

        qps_off, qps_on, step_off, step_on = [], [], [], []
        ev0, span0 = _flight_counts()
        on_wall = 0.0
        requests_on = 0
        for i in range(rounds):
            # ABBA: alternate which arm goes first so slow drift (CPU
            # frequency, page cache, the ~2x box noise) cancels instead
            # of biasing whichever arm always runs second
            order = (False, True) if i % 2 == 0 else (True, False)
            for on in order:
                t_arm = time.perf_counter()
                q, s = measure_arm(on)
                (qps_on if on else qps_off).append(q)
                (step_on if on else step_off).append(s)
                if on:
                    on_wall += time.perf_counter() - t_arm
                    requests_on += int(q * serve_s)
        ev1, span1 = _flight_counts()

        # per-event cost microbenches, in this process, plane ON
        set_plane(True)
        n_micro = 3000
        t0 = time.perf_counter()
        for _ in range(n_micro):
            rec.record("bench_probe", dur_s=0.001, n=1)
        cost_record_us = (time.perf_counter() - t0) / n_micro * 1e6
        t0 = time.perf_counter()
        for _ in range(n_micro):
            ttracing.emit_span("bench_probe", 0.0, 0.001,
                               trace_id="bench", model="m")
        cost_span_us = (time.perf_counter() - t0) / n_micro * 1e6
        t0 = time.perf_counter()
        for _ in range(20):
            monitoring.to_prometheus()
        cost_scrape_us = (time.perf_counter() - t0) / 20 * 1e6 * 2.0
        # (x2: HTTP framing/handler roughly doubles the render cost)
        server.close()
        train_sess.close()
        telemetry.shutdown()
    finally:
        shutil.rmtree(tmp, ignore_errors=True)
    q_off = float(np.median(qps_off))
    q_on = float(np.median(qps_on))
    s_off = float(np.median(step_off))
    s_on = float(np.median(step_on))
    # informational A/B: median of PAIRED per-round ratios (adjacent
    # windows share box weather) + the noise floor that bounds what
    # this method can resolve
    q_ratios = [on / max(off, 1e-9)
                for on, off in zip(qps_on, qps_off)]
    s_ratios = [on / max(off, 1e-12)
                for on, off in zip(step_on, step_off)]
    ab_serving = 1.0 - float(np.median(q_ratios))
    ab_train = float(np.median(s_ratios)) - 1.0
    qps_cv = float(np.std(qps_off) / max(np.mean(qps_off), 1e-9))

    # pinned overhead: measured per-event costs x measured event rates,
    # conservatively charged as fully-serialized microseconds
    span_events = max(span1 - span0, 0)
    other_events = max((ev1 - ev0) - span_events, 0)
    reqs = max(requests_on, 1)
    spans_per_req = span_events / reqs
    other_per_req = other_events / reqs
    overhead_us_per_req = (spans_per_req * cost_span_us
                           + other_per_req * cost_record_us)
    scrape_rate = scrapes[0] / max(on_wall, 1e-9)
    scrape_frac = scrape_rate * cost_scrape_us / 1e6
    serving_overhead = overhead_us_per_req * q_on / 1e6 + scrape_frac
    # train: run events sampled 1/16 (see session.py)
    train_overhead = (cost_record_us / 16.0) / max(s_on * 1e6, 1e-9) \
        + scrape_frac
    worst = max(serving_overhead, train_overhead)
    return {
        **_monitoring_info(),
        "metric": "telemetry_overhead_frac",
        "value": round(worst, 4),
        "unit": "fraction (worst of serving/train accounted overhead: "
                "measured per-event cost x measured event rate, "
                "serialized-worst-case; telemetry plane fully ON)",
        "vs_baseline": None,
        "budget": 0.03,
        "within_budget": bool(worst < 0.03),
        "serving_overhead_frac": round(serving_overhead, 4),
        "train_overhead_frac": round(train_overhead, 4),
        "cost_record_us": round(cost_record_us, 2),
        "cost_span_us": round(cost_span_us, 2),
        "cost_scrape_us": round(cost_scrape_us, 1),
        "spans_per_request": round(spans_per_req, 2),
        "other_events_per_request": round(other_per_req, 3),
        "scrapes_per_s": round(scrape_rate, 2),
        "ab_serving_overhead_frac": round(ab_serving, 4),
        "ab_train_overhead_frac": round(ab_train, 4),
        "ab_qps_noise_cv": round(qps_cv, 3),
        "ab_note": ("ab_* are paired-ABBA wall-clock medians; with "
                    "ab_qps_noise_cv this large they bound, not "
                    "resolve, a 3% effect — the pinned value is the "
                    "accounted overhead above"),
        "qps_on": round(q_on, 1), "qps_off": round(q_off, 1),
        "step_ms_on": round(s_on * 1e3, 4),
        "step_ms_off": round(s_off * 1e3, 4),
        "qps_on_rounds": [round(v, 1) for v in qps_on],
        "qps_off_rounds": [round(v, 1) for v in qps_off],
        "step_ms_on_rounds": [round(v * 1e3, 4) for v in step_on],
        "step_ms_off_rounds": [round(v * 1e3, 4) for v in step_off],
        "metrics_scrapes_during_on": scrapes[0],
        "scrape_errors": scrape_errors[:3],
        "rounds": rounds,
        "n_clients": n_clients,
        "train_steps_per_round": train_steps,
        "flight_recorder": rec.stats(),
        "device": str(jax.devices()[0]),
    }


def _measure_sync(platform, device_kind):
    """Sync row (ISSUE 18): overhead of the lock-order witness
    (platform/sync.py — named/ranked locks, held stacks, edge
    recording) on the serving and fused-train configs, witness ON vs
    OFF (``sync.set_witness_enabled``).

    Same split accounting as the telemetry row, because the witness
    cost (~1 us per acquisition) sits far under this box's wall-clock
    noise floor:

    - A/B medians of PAIRED ABBA rounds (``ab_*``): informational.
    - The PINNED overhead (``value``): the measured per-acquisition
      cost DELTA (uncontended acquire+release microbenched in this
      process, witness ON minus OFF) x measured acquisition rates
      (the sync acquire counter during the ON rounds), conservatively
      charged as fully-serialized microseconds.

    The acceptance bar pins the WORST of the serving and fused-train
    accounted fractions < 3%."""
    import shutil
    import tempfile
    import threading

    import jax

    import simple_tensorflow_tpu as stf
    from simple_tensorflow_tpu import saved_model as sm
    from simple_tensorflow_tpu import serving
    from simple_tensorflow_tpu.data.dataset import Dataset
    from simple_tensorflow_tpu.platform import sync

    rounds = int(os.environ.get("BENCH_SYNC_ROUNDS", "4"))
    serve_s = float(os.environ.get("BENCH_SYNC_SECONDS", "1.5"))
    n_clients = 8
    n_fused = 64
    train_steps = int(os.environ.get("BENCH_SYNC_TRAIN_STEPS", "192"))
    in_dim, hidden, classes = 128, 256, 10
    rng = np.random.RandomState(0)

    # -- serving arm (same mini-model as the telemetry row) ------------------
    x = stf.placeholder(stf.float32, [None, in_dim], name="x")
    w1 = stf.Variable(stf.constant(
        (rng.randn(in_dim, hidden) * 0.05).astype(np.float32)), name="w1")
    w2 = stf.Variable(stf.constant(
        (rng.randn(hidden, classes) * 0.05).astype(np.float32)),
        name="w2")
    probs = stf.nn.softmax(stf.matmul(stf.tanh(stf.matmul(x, w1)), w2),
                           name="probs")
    tmp = tempfile.mkdtemp(prefix="stf_bench_sync_")
    export_dir = os.path.join(tmp, "model")
    with stf.Session() as sess:
        sess.run(stf.global_variables_initializer())
        sm.simple_save(sess, export_dir, inputs={"x": x},
                       outputs={"probs": probs})
    stf.reset_default_graph()
    examples = rng.randn(64, in_dim).astype(np.float32)

    def serving_round(server, seconds):
        counts = [0] * n_clients
        gate = threading.Barrier(n_clients + 1)
        stop_at = [0.0]

        def client(i):
            gate.wait()
            j = i
            while time.perf_counter() < stop_at[0]:
                server.predict({"x": examples[j % 64]}).result(
                    timeout=120)
                counts[i] += 1
                j += n_clients
        threads = [threading.Thread(target=client, args=(i,),
                                    daemon=True,
                                    name=f"stf_bench_sync_client_{i}")
                   for i in range(n_clients)]
        for t in threads:
            t.start()
        t0 = time.perf_counter()
        stop_at[0] = t0 + seconds
        gate.wait()
        for t in threads:
            t.join()
        return sum(counts) / (time.perf_counter() - t0)

    # -- fused-train arm (device-resident run_steps windows: the ring
    # buffer / worker pool / session locks are the traffic under test) -------
    g = stf.Graph()
    with g.as_default():
        xt = stf.placeholder(stf.float32, [8, in_dim], name="xt")
        wt = stf.get_variable(
            "wt", [in_dim, in_dim],
            initializer=stf.random_normal_initializer(stddev=0.05))
        loss = stf.reduce_sum(stf.matmul(xt, wt))
        opt = stf.train.GradientDescentOptimizer(1e-4).minimize(loss)
        train_sess = stf.Session(graph=g)
        with g.as_default():
            train_sess.run(stf.global_variables_initializer())
    batch_np = {"xt": np.ones((8, in_dim), np.float32)}
    fetch = [opt, loss]

    def batch_stream():
        while True:
            yield dict(batch_np)

    with g.as_default():
        train_ds = Dataset.from_generator(
            batch_stream).prefetch_to_device(buffer_size=2,
                                             superbatch=n_fused)
    train_it = iter(train_ds)

    def train_round(steps):
        windows = max(1, steps // n_fused)
        t0 = time.perf_counter()
        for _ in range(windows):
            sb = {xt: next(train_it)["xt"]}
            out = train_sess.run_steps(fetch, n=n_fused,
                                       stacked_feeds=sb,
                                       output_mode="stacked")
            np.asarray(out[1])
        return (time.perf_counter() - t0) / (windows * n_fused)

    try:
        server = serving.ModelServer(policy=serving.BatchingPolicy(
            max_batch_size=16, batch_timeout_ms=0.5,
            max_queue_depth=64))
        server.load(export_dir, name="bench_sync")
        for _ in range(4):  # warm every arm outside the clock
            server.predict({"x": examples[0]}).result(timeout=120)
        train_round(n_fused)

        qps_off, qps_on, step_off, step_on = [], [], [], []
        acq0_serve = acq1_serve = acq0_train = acq1_train = 0
        requests_on = 0
        steps_on = 0
        sync._set_count_acquires(True)
        for i in range(rounds):
            # ABBA: alternate which arm goes first so slow box drift
            # cancels instead of biasing the second arm
            order = (False, True) if i % 2 == 0 else (True, False)
            for on in order:
                sync.set_witness_enabled(on)
                if on:
                    acq0_serve = sync._set_count_acquires(True)
                q = serving_round(server, serve_s)
                if on:
                    acq1_serve = sync._set_count_acquires(True)
                s = train_round(train_steps)
                if on:
                    acq1_train = sync._set_count_acquires(True)
                    requests_on += int(q * serve_s)
                    steps_on += train_steps
                (qps_on if on else qps_off).append(q)
                (step_on if on else step_off).append(s)
                if on and i == 0:
                    # acquires per round are stable; one ON round's
                    # deltas give the rates
                    serve_acqs = acq1_serve - acq0_serve
                    train_acqs = acq1_train - acq1_serve
        sync.set_witness_enabled(True)

        # per-acquisition cost microbench: uncontended acquire+release
        # of one named lock, witness ON vs OFF — the delta is what the
        # witness layer itself costs on the hot path
        probe = sync.Lock("bench/sync_probe", rank=sync.LEAF)
        n_micro = 20000

        def acq_cost_us():
            t0 = time.perf_counter()
            for _ in range(n_micro):
                probe.acquire()
                probe.release()
            return (time.perf_counter() - t0) / n_micro * 1e6

        acq_cost_us()  # warm
        cost_on_us = acq_cost_us()
        sync.set_witness_enabled(False)
        cost_off_us = acq_cost_us()
        sync.set_witness_enabled(True)
        cost_delta_us = max(cost_on_us - cost_off_us, 0.0)

        server.close()
        train_sess.close()
    finally:
        sync._set_count_acquires(False)
        sync.set_witness_enabled(True)
        shutil.rmtree(tmp, ignore_errors=True)

    q_off = float(np.median(qps_off))
    q_on = float(np.median(qps_on))
    s_off = float(np.median(step_off))
    s_on = float(np.median(step_on))
    q_ratios = [on / max(off, 1e-9)
                for on, off in zip(qps_on, qps_off)]
    s_ratios = [on / max(off, 1e-12)
                for on, off in zip(step_on, step_off)]
    ab_serving = 1.0 - float(np.median(q_ratios))
    ab_train = float(np.median(s_ratios)) - 1.0
    qps_cv = float(np.std(qps_off) / max(np.mean(qps_off), 1e-9))

    # pinned: acquires/unit x per-acquire witness delta, serialized
    one_round_reqs = max(requests_on // max(rounds, 1), 1)
    acq_per_req = serve_acqs / max(one_round_reqs, 1)
    acq_per_step = train_acqs / max(train_steps, 1)
    serving_overhead = acq_per_req * cost_delta_us * q_on / 1e6
    train_overhead = (acq_per_step * cost_delta_us
                      / max(s_on * 1e6, 1e-9))
    worst = max(serving_overhead, train_overhead)
    return {
        **_monitoring_info(),
        "metric": "sync_witness_overhead_frac",
        "value": round(worst, 4),
        "unit": "fraction (worst of serving/fused-train accounted "
                "overhead: measured per-acquire witness cost x "
                "measured acquire rate, serialized-worst-case)",
        "vs_baseline": None,
        "budget": 0.03,
        "within_budget": bool(worst < 0.03),
        "serving_overhead_frac": round(serving_overhead, 4),
        "train_overhead_frac": round(train_overhead, 6),
        "cost_acquire_on_us": round(cost_on_us, 3),
        "cost_acquire_off_us": round(cost_off_us, 3),
        "cost_acquire_delta_us": round(cost_delta_us, 3),
        "acquires_per_request": round(acq_per_req, 1),
        "acquires_per_fused_step": round(acq_per_step, 2),
        "witness": {k: v for k, v in sync.witness_snapshot().items()
                    if k in ("enabled",)},
        "witness_edges": len(sync.witness_snapshot()["edges"]),
        "potential_deadlocks": len(sync.potential_deadlocks()),
        "ab_serving_overhead_frac": round(ab_serving, 4),
        "ab_train_overhead_frac": round(ab_train, 4),
        "ab_qps_noise_cv": round(qps_cv, 3),
        "ab_note": ("ab_* are paired-ABBA wall-clock medians; the "
                    "~1 us/acquire witness cost sits under this box's "
                    "noise floor — the pinned value is the accounted "
                    "overhead above"),
        "qps_on": round(q_on, 1), "qps_off": round(q_off, 1),
        "step_ms_on": round(s_on * 1e3, 4),
        "step_ms_off": round(s_off * 1e3, 4),
        "n_fused": n_fused,
        "rounds": rounds,
        "n_clients": n_clients,
        "train_steps_per_round": train_steps,
        "device": str(jax.devices()[0]),
    }


def _measure_memory(platform, device_kind):
    """Memory row (ISSUE 13 satellite): the telemetry-plane overhead
    re-measured with the HBM ledger ON — the combined plane (flight
    recorder + request tracing + live /metrics scraper + ledger
    accounting on every state commit) must still clear the <3% serving
    budget — plus the ledger-vs-``jax.live_arrays()`` reconciliation
    drift on the live serving workload.

    Same accounting method as the telemetry row (this box's wall-clock
    noise cannot resolve 3%): measured per-event costs x measured event
    rates, charged fully serialized. The ledger's contribution is the
    per-commit ``sync_ledger`` fast path (one dict-view comparison per
    run/batch) plus the register/release pair amortized over churn."""
    import gc
    import shutil
    import tempfile
    import threading
    import urllib.request

    import jax

    import simple_tensorflow_tpu as stf
    from simple_tensorflow_tpu import saved_model as sm
    from simple_tensorflow_tpu import serving, telemetry
    from simple_tensorflow_tpu.platform import monitoring
    from simple_tensorflow_tpu.telemetry import memory as memory_mod
    from simple_tensorflow_tpu.telemetry import tracing as ttracing

    rounds = int(os.environ.get("BENCH_MEMORY_ROUNDS", "3"))
    serve_s = float(os.environ.get("BENCH_MEMORY_SECONDS", "1.5"))
    n_clients = 8
    train_steps = int(os.environ.get("BENCH_MEMORY_TRAIN_STEPS", "300"))
    in_dim, hidden, classes = 128, 256, 10
    rng = np.random.RandomState(0)

    x = stf.placeholder(stf.float32, [None, in_dim], name="x")
    w1 = stf.Variable(stf.constant(
        (rng.randn(in_dim, hidden) * 0.05).astype(np.float32)),
        name="w1")
    w2 = stf.Variable(stf.constant(
        (rng.randn(hidden, classes) * 0.05).astype(np.float32)),
        name="w2")
    probs = stf.nn.softmax(stf.matmul(stf.tanh(stf.matmul(x, w1)), w2),
                           name="probs")
    tmp = tempfile.mkdtemp(prefix="stf_bench_memory_")
    export_dir = os.path.join(tmp, "model")
    with stf.Session() as sess:
        sess.run(stf.global_variables_initializer())
        sm.simple_save(sess, export_dir, inputs={"x": x},
                       outputs={"probs": probs})
    stf.reset_default_graph()
    examples = rng.randn(64, in_dim).astype(np.float32)

    def serving_round(server, seconds):
        counts = [0] * n_clients
        gate = threading.Barrier(n_clients + 1)
        stop_at = [0.0]

        def client(i):
            gate.wait()
            j = i
            while time.perf_counter() < stop_at[0]:
                server.predict({"x": examples[j % 64]}).result(
                    timeout=120)
                counts[i] += 1
                j += n_clients
        threads = [threading.Thread(target=client, args=(i,),
                                    daemon=True)
                   for i in range(n_clients)]
        for t in threads:
            t.start()
        t0 = time.perf_counter()
        stop_at[0] = t0 + seconds
        gate.wait()
        for t in threads:
            t.join()
        return sum(counts) / (time.perf_counter() - t0)

    g = stf.Graph()
    with g.as_default():
        xt = stf.placeholder(stf.float32, [32, in_dim], name="xt")
        wt = stf.get_variable(
            "wt", [in_dim, in_dim],
            initializer=stf.random_normal_initializer(stddev=0.05))
        loss = stf.reduce_sum(stf.matmul(xt, wt))
        opt = stf.train.GradientDescentOptimizer(1e-4).minimize(loss)
        train_sess = stf.Session(graph=g)
        with g.as_default():
            train_sess.run(stf.global_variables_initializer())
    feed = {xt: np.ones((32, in_dim), np.float32)}

    def train_round(steps):
        train_sess.run(opt, feed)
        t0 = time.perf_counter()
        for _ in range(steps):
            train_sess.run(opt, feed)
        return (time.perf_counter() - t0) / steps

    rec = telemetry.get_recorder()
    rec.set_enabled(True)
    ttracing.set_enabled(True)
    led = memory_mod.get_ledger()
    scrape_errors = []
    try:
        server = serving.ModelServer(policy=serving.BatchingPolicy(
            max_batch_size=16, batch_timeout_ms=0.5,
            max_queue_depth=64))
        server.load(export_dir, name="bench_memory")
        for _ in range(4):
            server.predict({"x": examples[0]}).result(timeout=120)
        train_round(8)

        tsrv = telemetry.start(port=0)
        scrape_stop = threading.Event()
        scrapes = [0]

        def scraper():
            # 1 Hz — the densest REAL Prometheus cadence (production is
            # 15-60 s; the telemetry row's 250 ms deliberately
            # over-samples to make exporter cost visible, this row's
            # budget verdict charges a cadence a fleet would run)
            while not scrape_stop.is_set():
                try:
                    with urllib.request.urlopen(
                            tsrv.url + "/metrics", timeout=10) as r:
                        r.read()
                    with urllib.request.urlopen(
                            tsrv.url + "/memz", timeout=10) as r:
                        r.read()
                    scrapes[0] += 1
                except Exception as e:  # noqa: BLE001
                    scrape_errors.append(repr(e))
                scrape_stop.wait(1.0)

        def _counter_total(name):
            snap = monitoring.export().get(name, {})
            cells = snap.get("cells") or {}
            return sum(cells.values())

        scrape_stop.clear()
        th = threading.Thread(target=scraper, daemon=True,
                              name="stf_bench_scraper")
        th.start()
        def _span_total():
            snap = monitoring.export().get(
                "/stf/telemetry/flight_events", {})
            return (snap.get("cells") or {}).get("span", 0)

        qps_rounds, step_rounds = [], []
        ev0 = _counter_total("/stf/telemetry/flight_events")
        span0 = _span_total()
        batches0 = _counter_total("/stf/serving/batches")
        on_wall_t0 = time.perf_counter()
        requests_on = 0
        for _ in range(rounds):
            q = serving_round(server, serve_s)
            s = train_round(train_steps)
            qps_rounds.append(q)
            step_rounds.append(s)
            requests_on += int(q * serve_s)
        on_wall = time.perf_counter() - on_wall_t0
        ev1 = _counter_total("/stf/telemetry/flight_events")
        span1 = _span_total()
        batches1 = _counter_total("/stf/serving/batches")
        scrape_stop.set()
        th.join(10)

        # per-event cost microbenches, this process, plane fully ON
        n_micro = 3000
        t0 = time.perf_counter()
        for _ in range(n_micro):
            rec.record("bench_probe", dur_s=0.001, n=1)
        cost_record_us = (time.perf_counter() - t0) / n_micro * 1e6
        t0 = time.perf_counter()
        for _ in range(n_micro):
            ttracing.emit_span("bench_probe", 0.0, 0.001,
                               trace_id="bench", model="m")
        cost_span_us = (time.perf_counter() - t0) / n_micro * 1e6
        t0 = time.perf_counter()
        for _ in range(20):
            monitoring.to_prometheus()
        cost_scrape_us = (time.perf_counter() - t0) / 20 * 1e6 * 2.0
        # the ledger's hot-path contribution: the per-commit fast path
        # (unchanged key set — every steady-state step)...
        store = train_sess._variable_store
        t0 = time.perf_counter()
        for _ in range(20000):
            store.sync_ledger()
        cost_sync_us = (time.perf_counter() - t0) / 20000 * 1e6
        # ...and the register/release pair (store churn, snapshots)
        t0 = time.perf_counter()
        for _ in range(n_micro):
            led.release(led.register("bench_probe", 1024,
                                     memory_mod.CLASS_STATE, "bench"))
        cost_reg_pair_us = (time.perf_counter() - t0) / n_micro * 1e6

        ledger_snapshot = led.snapshot(top=5)
        server.close()
        # reconciliation after the serving plane quiesces (the batcher
        # thread's last in-flight batch pins a few hundred device
        # bytes while it waits for work); the training session's store
        # stays live and must fully attribute (acceptance: drift 0)
        gc.collect()
        reconcile = led.reconcile()
        train_sess.close()
        telemetry.shutdown()
    finally:
        shutil.rmtree(tmp, ignore_errors=True)

    q_on = float(np.median(qps_rounds))
    s_on = float(np.median(step_rounds))
    reqs = max(requests_on, 1)
    batches = max(batches1 - batches0, 1)
    span_events = max(span1 - span0, 0)
    other_events = max((ev1 - ev0) - span_events, 0)
    events_per_req = (span_events + other_events) / reqs
    # per-request telemetry cost (same split accounting as the
    # telemetry row) + one ledger sync per executed batch, amortized
    overhead_us_per_req = (span_events / reqs * cost_span_us
                           + other_events / reqs * cost_record_us
                           + cost_sync_us * batches / reqs)
    scrape_rate = scrapes[0] / max(on_wall, 1e-9)
    scrape_frac = scrape_rate * cost_scrape_us / 1e6
    serving_overhead = overhead_us_per_req * q_on / 1e6 + scrape_frac
    # train: sampled run events (1/16) + one ledger sync per step
    train_overhead = ((cost_record_us / 16.0 + cost_sync_us)
                      / max(s_on * 1e6, 1e-9)) + scrape_frac
    worst = max(serving_overhead, train_overhead)
    return {
        **_monitoring_info(),
        "metric": "memory_plane_overhead_frac",
        "value": round(worst, 4),
        "unit": "fraction (worst of serving/train accounted overhead: "
                "telemetry plane + HBM ledger fully ON, measured "
                "per-event cost x measured event rate, serialized "
                "worst case)",
        "vs_baseline": None,
        "budget": 0.03,
        "within_budget": bool(worst < 0.03),
        "serving_overhead_frac": round(serving_overhead, 4),
        "train_overhead_frac": round(train_overhead, 4),
        "cost_ledger_sync_us": round(cost_sync_us, 3),
        "cost_ledger_register_release_us": round(cost_reg_pair_us, 2),
        "cost_record_us": round(cost_record_us, 2),
        "cost_span_us": round(cost_span_us, 2),
        "cost_scrape_us": round(cost_scrape_us, 1),
        "events_per_request": round(events_per_req, 3),
        "batches_per_request": round(batches / reqs, 3),
        "scrapes_per_s": round(scrape_rate, 2),
        "scrape_errors": scrape_errors[:3],
        "qps": round(q_on, 1),
        "step_ms": round(s_on * 1e3, 4),
        "rounds": rounds,
        "reconcile_drift_bytes": int(reconcile["untracked_bytes"]),
        "reconcile": {k: v for k, v in reconcile.items()
                      if k != "untracked_top"},
        "ledger": ledger_snapshot,
        "device": str(jax.devices()[0]),
    }


def _measure_kernel_tier(platform, device_kind):
    """Kernel-tier row (ISSUE 11 tentpole): two halves.

    (1) Optimizer-tail A/B on the BERT small-step config (the
    loop_fusion CPU regime — tiny hidden so the step is tail/dispatch
    dominated, at BERT-base DEPTH so the variable inventory is real:
    12 layers / hidden 16 / batch 1 / seq 8, ~206 trainable variables;
    base on TPU): a tail-only program — device-resident synthetic
    gradients (param * 1e-3, no feeds) into ONE apply_gradients —
    timed with the per-variable assign chains + per-variable slots
    (kernel registry OFF at graph build) vs the fused
    flattened-parameter update over per-group FLAT slot variables
    (AUTO), interleaved A/B/A/B, median of 3 each. This isolates
    exactly the per-step tail every training step pays after the
    backward pass: N update chains + 2N slot arrays threaded through
    the step vs one batched update + O(groups) arrays.

    (2) Per-kernel routed-vs-fallback timings: each registered kernel
    pair timed on a representative shape (best-of-3 under jit, compile
    excluded — the registry's own autotune harness), recorded into the
    registry's measured-verdict cache (kreg.record_measurement), so
    the auto-mode verdict recorded in this artifact is BY CONSTRUCTION
    never the lowering these measurements showed slower — and the
    consistency bit re-checks it."""
    steps = int(os.environ.get("BENCH_KERNEL_STEPS", "100"))
    warmup = 5

    import jax

    import simple_tensorflow_tpu as stf
    from simple_tensorflow_tpu.kernels import registry as kreg
    from simple_tensorflow_tpu.models import bert
    from simple_tensorflow_tpu.ops.pallas import flat_group_key

    cfg = bert.BertConfig.base()
    batch, seq_len, max_pred = 24, 512, 76
    if platform == "cpu":
        cfg = bert.BertConfig(
            vocab_size=99, hidden_size=16, num_layers=12, num_heads=2,
            intermediate_size=32, max_position=8, hidden_dropout=0.0,
            attention_dropout=0.0)
        batch, seq_len, max_pred = 1, 8, 1

    def build_tail(mode):
        """Fresh graph: BERT's variable inventory + a tail-only
        apply_gradients driven by device-resident synthetic grads."""
        kreg.set_mode(mode)
        kreg.clear_decisions()
        stf.reset_default_graph()
        bert.bert_pretrain_model(
            batch_size=batch, seq_len=seq_len, max_predictions=max_pred,
            cfg=cfg, compute_dtype=stf.float32, use_input_mask=True)
        tvars = stf.trainable_variables()
        grads = [v.read_value() * stf.constant(1e-3) for v in tvars]
        opt = stf.train.AdamOptimizer(1e-3)
        train = opt.apply_gradients(list(zip(grads, tvars)))
        sess = stf.Session()
        sess.run(stf.global_variables_initializer())
        fused_types = {o.type
                       for o in stf.get_default_graph().get_operations()}
        return sess, train, len(tvars), \
            "FusedAdamUpdate" in fused_types

    def time_tail(sess, train):
        for _ in range(warmup):
            sess.run(train)
        t0 = time.perf_counter()
        for _ in range(steps):
            sess.run(train)
        return (time.perf_counter() - t0) / steps

    sess_pv, train_pv, n_vars, pv_fused = build_tail("off")
    sess_f, train_f, _, f_fused = build_tail("auto")
    kreg.set_mode(None)
    assert not pv_fused and f_fused, "mode gating failed at graph build"
    pv_times, f_times = [], []
    for _ in range(3):  # interleaved A/B, median of 3
        pv_times.append(time_tail(sess_pv, train_pv))
        f_times.append(time_tail(sess_f, train_f))
    pv_s = float(np.median(pv_times))
    fused_s = float(np.median(f_times))
    sess_pv.close()
    sess_f.close()

    # (2) per-kernel routed-vs-fallback timings + gating verdicts
    if platform == "cpu":
        rep_keys = {
            "FlashAttention": kreg.aval_key(
                np.zeros((1, 2, 64, 16), np.float32),
                np.zeros((1, 2, 64, 16), np.float32),
                np.zeros((1, 2, 64, 16), np.float32), None,
                causal=False, dropout=False),
            "FusedLayerNorm": kreg.aval_key(
                np.zeros((256, 256), np.float32),
                np.zeros((256,), np.float32),
                np.zeros((256,), np.float32)),
            "FusedSoftmaxXent": kreg.aval_key(
                np.zeros((64, 512), np.float32),
                np.zeros((64,), np.int32), label_smoothing=False),
            "QuantMatMul": kreg.aval_key(
                np.zeros((64, 128), np.float32),
                np.zeros((128, 64), np.int8),
                np.zeros((64,), np.float32)),
            "FusedDropoutBiasResidual": kreg.aval_key(
                np.zeros((256, 128), np.float32),
                np.zeros((256, 128), np.float32), None, rate=0.1),
            "FusedAdamUpdate": flat_group_key(8192, "float32", "float32"),
            "FusedMomentumUpdate": flat_group_key(8192, "float32",
                                                  "float32"),
        }
    else:
        rep_keys = {
            "FlashAttention": kreg.aval_key(
                np.zeros((4, 16, 1024, 64), np.float32),
                np.zeros((4, 16, 1024, 64), np.float32),
                np.zeros((4, 16, 1024, 64), np.float32), None,
                causal=False, dropout=False),
            "FusedLayerNorm": kreg.aval_key(
                np.zeros((8192, 1024), np.float32),
                np.zeros((1024,), np.float32),
                np.zeros((1024,), np.float32)),
            "FusedSoftmaxXent": kreg.aval_key(
                np.zeros((4096, 32768), np.float32),
                np.zeros((4096,), np.int32), label_smoothing=False),
            "QuantMatMul": kreg.aval_key(
                np.zeros((1024, 4096), np.float32),
                np.zeros((4096, 4096), np.int8),
                np.zeros((4096,), np.float32)),
            "FusedDropoutBiasResidual": kreg.aval_key(
                np.zeros((16384, 1024), np.float32),
                np.zeros((16384, 1024), np.float32), None, rate=0.1),
            "FusedAdamUpdate": flat_group_key(1 << 24, "float32",
                                              "float32"),
            "FusedMomentumUpdate": flat_group_key(1 << 24, "float32",
                                                  "float32"),
        }
    per_kernel = {}
    gating_consistent = True
    for op_type, key in rep_keys.items():
        kd = kreg._KERNELS[op_type]
        args, kwargs = kd.make_case(key)
        static_impl, static_reason = kreg.decide(op_type, key,
                                                 mode="auto", count=False)
        t_p = kreg._time_thunk(kd.impls["pallas"], args, kwargs)
        t_x = kreg._time_thunk(kd.impls["xla"], args, kwargs)
        # feed the measurement into the autotune cache: auto-mode
        # decisions from here on follow it ("auto never picks a
        # lowering the autotune measured slower")
        kreg.record_measurement(op_type, key, t_p, t_x)
        impl, reason = kreg.decide(op_type, key, mode="auto",
                                   count=False)
        chosen, other = (t_p, t_x) if impl == "pallas" else (t_x, t_p)
        ok = chosen <= other
        gating_consistent = gating_consistent and ok
        per_kernel[op_type] = {
            "pallas_s": round(t_p, 6), "xla_s": round(t_x, 6),
            "routed_over_fallback": round(t_p / max(t_x, 1e-12), 3),
            "static_verdict": static_impl, "static_reason": static_reason,
            "auto_verdict": impl, "auto_reason": reason,
            "consistent": ok,
        }

    return {
        **_monitoring_info(),
        "metric": "kernel_tier_fused_optimizer_tail_speedup",
        "value": round(pv_s / max(fused_s, 1e-12), 3),
        "unit": "x (per-variable assign tail / fused update, BERT "
                "small-step config)",
        "vs_baseline": None,
        "per_variable_tail_ms": round(pv_s * 1e3, 3),
        "fused_tail_ms": round(fused_s * 1e3, 3),
        "n_variables": n_vars,
        "interleaved_runs": 3,
        "per_kernel": per_kernel,
        "gating_consistent": bool(gating_consistent),
        "kernels_snapshot": kreg.snapshot(),
        "device": str(jax.devices()[0]),
    }


def _measure_checkpoint(platform, device_kind):
    """stf.checkpoint row (ISSUE 10): step-loop stall of an async save
    (barrier snapshot + enqueue, background stf_ckpt_writer commit) vs
    a blocking ``Saver.save`` of the SAME state, plus restore time and
    the steps/sec of a save-every-K training loop under each mode. The
    headline is the stall ratio (acceptance: async cuts the stall
    >=5x). Medians over several saves, interleaved ABAB so filesystem
    cache drift hits both modes alike."""
    import shutil
    import tempfile

    import simple_tensorflow_tpu as stf
    from simple_tensorflow_tpu import checkpoint as ckpt_mod

    reps = int(os.environ.get("BENCH_CKPT_REPS", "5"))
    # ~64 MB of f32 state: big enough that serialize+fsync dominates a
    # blocking save, small enough for the CPU fallback box
    dim = int(os.environ.get("BENCH_CKPT_DIM", "2048"))
    stf.reset_default_graph()
    rng = np.random.RandomState(0)
    gs = stf.train.get_or_create_global_step()
    train_ops = [stf.assign_add(gs, stf.constant(1, stf.int64))]
    for i in range(4):
        v = stf.Variable(stf.constant(
            rng.randn(dim, dim).astype(np.float32) * 0.01), name=f"w{i}")
        train_ops.append(stf.assign_add(
            v._ref, stf.fill([dim, dim], stf.constant(1e-4))))
    train = stf.group(*train_ops)
    sess = stf.Session()
    sess.run(stf.global_variables_initializer())
    sess.run_steps(train, n=4)  # warm the fused path (donation active)
    state_bytes = 4 * dim * dim * 4

    tmp = tempfile.mkdtemp(prefix="stf_bench_ckpt_")
    try:
        blocking_saver = stf.train.Saver(max_to_keep=2)
        mgr = ckpt_mod.CheckpointManager(
            os.path.join(tmp, "async"), max_to_keep=2, async_save=True)

        blocking_stalls, async_stalls = [], []
        for _ in range(reps):  # interleaved ABAB
            t0 = time.perf_counter()
            blocking_saver.save(sess, os.path.join(tmp, "blk", "ckpt"),
                                global_step=gs, write_meta_graph=False)
            blocking_stalls.append(time.perf_counter() - t0)
            sess.run_steps(train, n=2)
            t0 = time.perf_counter()
            mgr.save(sess, global_step=gs)
            async_stalls.append(time.perf_counter() - t0)
            mgr.wait_until_finished()  # keep runs independent
            sess.run_steps(train, n=2)
        blocking_s = float(np.median(blocking_stalls))
        async_s = float(np.median(async_stalls))

        # integrated loop: steps/sec with a save every K windows — the
        # end-to-end view of what the stall costs a real training loop
        def loop_steps_per_sec(save_fn, n_windows=6, window=8):
            sess.run_steps(train, n=window)
            t0 = time.perf_counter()
            for _ in range(n_windows):
                sess.run_steps(train, n=window)
                save_fn()
            dur = time.perf_counter() - t0
            mgr.wait_until_finished()
            return n_windows * window / dur

        sps_async = loop_steps_per_sec(
            lambda: mgr.save(sess, global_step=gs))
        sps_blocking = loop_steps_per_sec(
            lambda: blocking_saver.save(
                sess, os.path.join(tmp, "blk", "ckpt"), global_step=gs,
                write_meta_graph=False))
        # final committed save of the CURRENT state, so the restored
        # session can be value-checked against the live one
        mgr.save(sess, global_step=gs, blocking=True)

        t0 = time.perf_counter()
        restore_sess = stf.Session()
        mgr.restore(restore_sess)
        restore_s = time.perf_counter() - t0
        ok = bool(np.allclose(
            np.asarray(restore_sess.variable_value("w0")),
            np.asarray(sess.variable_value("w0"))))
        ckpt_mod.shutdown_writer()
    finally:
        shutil.rmtree(tmp, ignore_errors=True)

    ratio = blocking_s / max(async_s, 1e-9)
    return {
        **_monitoring_info(),
        "metric": "checkpoint_async_stall_speedup_vs_blocking",
        "value": round(ratio, 2),
        "unit": "x (blocking Saver.save stall / async manager.save stall)",
        "vs_baseline": None,
        "blocking_save_stall_s": round(blocking_s, 6),
        "async_save_stall_s": round(async_s, 6),
        "restore_s": round(restore_s, 4),
        "restore_values_match": ok,
        "steps_per_sec_async_saves": round(sps_async, 2),
        "steps_per_sec_blocking_saves": round(sps_blocking, 2),
        "state_bytes": state_bytes,
        "reps": reps,
        "note": ("stall = wall time the step loop spends inside the "
                 "save call; async pays only the donation-safe device "
                 "snapshot + enqueue, the stf_ckpt_writer thread "
                 "commits (atomic temp+fsync+replace, sha256 in the "
                 "index) while the next fused window runs"),
    }


def _measure_transformer(batch, platform, device_kind):
    """BASELINE config 5: Transformer-big WMT en-de training step +
    beam-search inference latency. Comparator 2000 tokens/sec is a
    P100-era per-GPU transformer-big rate (same vintage as the other
    baselines)."""
    steps = int(os.environ.get("BENCH_STEPS", "20"))
    warmup = 3
    src_len = tgt_len = int(os.environ.get("BENCH_TFMR_SEQ", "64"))

    import jax
    import jax.numpy as jnp

    import simple_tensorflow_tpu as stf
    from simple_tensorflow_tpu.models import transformer

    cfg = transformer.TransformerConfig.big()
    if platform == "cpu":
        cfg = transformer.TransformerConfig.tiny()
        batch, src_len, tgt_len, steps, warmup = 4, 16, 16, 3, 1

    stf.reset_default_graph()
    m = transformer.transformer_train_model(
        batch_size=batch, src_len=src_len, tgt_len=tgt_len, cfg=cfg,
        recompute=os.environ.get("BENCH_TFMR_RECOMPUTE", "0") == "1")
    b = transformer.synthetic_wmt_batch(batch, src_len, tgt_len,
                                        vocab_size=cfg.vocab_size)
    feed = {m[k]: jnp.asarray(v) for k, v in b.items()}
    sess = stf.Session()
    sess.run(stf.global_variables_initializer())
    t0 = time.perf_counter()
    for _ in range(warmup):
        sess.run(m["train_op"], feed_dict=feed)
    _ = sess.run(m["loss"], feed_dict=feed)
    compile_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    for _ in range(steps):
        sess.run(m["train_op"], feed_dict=feed)
    loss = sess.run(m["loss"], feed_dict=feed)
    dt = time.perf_counter() - t0
    sec_per_step = dt / (steps + 1)
    tokens_per_sec = batch * (src_len + tgt_len) / sec_per_step
    flops_per_token = 3.0 * transformer.transformer_flops_per_token(
        cfg, src_len, tgt_len)
    peak = detect_peak_flops(device_kind, platform)
    mfu = tokens_per_sec * flops_per_token / peak

    # beam-search inference latency (the model's flagship serving mode)
    beam_ms = None
    try:
        stf.reset_default_graph()
        infer_batch = 4
        src_ph = stf.placeholder(stf.int32, [infer_batch, src_len],
                                 name="beam_src")
        seqs, scores = transformer.beam_search_decode(
            src_ph, cfg=cfg, beam_size=4,
            decode_len=min(16, tgt_len))
        sess_i = stf.Session()
        sess_i.run(stf.global_variables_initializer())
        bfeed = {src_ph: b["src_ids"][:infer_batch]}
        # warm up the EXACT fetch signature of the timed loop (the step
        # cache keys on fetch names; a different fetch list recompiles)
        sess_i.run([seqs, scores], feed_dict=bfeed)
        t0 = time.perf_counter()
        n_iters = 5
        for _ in range(n_iters):
            sess_i.run([seqs, scores], feed_dict=bfeed)
        beam_ms = (time.perf_counter() - t0) / n_iters * 1000.0
    except Exception as e:
        beam_ms = f"failed: {type(e).__name__}: {str(e)[:200]}"

    result = {
        **_roofline_info(sess, feed, sec_per_step, platform),
        **_monitoring_info(),
        "metric": "transformer_big_tokens_per_sec_per_chip",
        "value": round(float(tokens_per_sec), 1),
        "unit": "tokens/sec/chip",
        "vs_baseline": round(float(tokens_per_sec) / 2000.0, 3),
        "mfu": round(float(mfu), 4),
        "batch": batch,
        "src_len": src_len,
        "tgt_len": tgt_len,
        "sec_per_step": round(sec_per_step, 5),
        "warmup_plus_compile_s": round(compile_s, 1),
        "loss": round(float(np.asarray(loss)), 4),
        "device": str(jax.devices()[0]),
    }
    if isinstance(beam_ms, float):
        result["beam_search_latency_ms"] = round(beam_ms, 1)
        result["beam_config"] = "batch4_beam4_len16"
    else:
        result["beam_search_latency_ms"] = beam_ms
    return result


def _measure_generative(platform, device_kind):
    """ISSUE 12: generative inference engine. Cached (KV-cache
    incremental) vs naive re-forward beam search at IDENTICAL token
    output — tokens/sec and p50 per-token latency — plus batch-fill
    fraction under open-loop join/leave churn through the token-level
    continuous-batching engine. Acceptance: >=5x tokens/sec on the CPU
    bench config with int-exact ids; churn fill >= 0.8."""
    import statistics

    import simple_tensorflow_tpu as stf
    from simple_tensorflow_tpu import serving
    from simple_tensorflow_tpu.models import transformer
    from simple_tensorflow_tpu.platform import monitoring

    # big enough that compute (not dispatch) dominates, small enough to
    # finish on the CPU bench box
    cfg = transformer.TransformerConfig(
        vocab_size=512, d_model=128, num_heads=4, d_ff=256,
        num_layers=2, dropout=0.0, max_len=64)
    b, k = 4, 4
    L = int(os.environ.get("BENCH_GEN_DECODE_LEN", "32"))
    src_len = 16
    reps = int(os.environ.get("BENCH_GEN_REPS", "3"))

    stf.reset_default_graph()
    stf.set_random_seed(0)
    src_ph = stf.placeholder(stf.int32, [b, src_len], "gen_src")
    ids_n, sc_n = transformer.beam_search_decode(
        src_ph, cfg=cfg, beam_size=k, decode_len=L,
        compute_dtype=stf.float32)
    ids_c, sc_c = transformer.beam_search_decode(
        src_ph, cfg=cfg, beam_size=k, decode_len=L,
        compute_dtype=stf.float32, use_cache=True)
    batch = transformer.synthetic_wmt_batch(b, src_len, src_len,
                                            vocab_size=cfg.vocab_size)
    feed = {src_ph: batch["src_ids"]}
    sess = stf.Session()
    sess.run(stf.global_variables_initializer())
    # warm the EXACT fetch signatures of the timed loops
    naive_ids, _ = sess.run([ids_n, sc_n], feed)
    cached_ids, cached_sc = sess.run([ids_c, sc_c], feed)
    ids_identical = bool(np.array_equal(np.asarray(naive_ids),
                                        np.asarray(cached_ids)))

    naive_t, cached_t = [], []
    for _ in range(reps):  # interleaved: same thermal/cache conditions
        t0 = time.perf_counter()
        sess.run([ids_n, sc_n], feed)
        naive_t.append(time.perf_counter() - t0)
        t0 = time.perf_counter()
        sess.run([ids_c, sc_c], feed)
        cached_t.append(time.perf_counter() - t0)
    naive_s = statistics.median(naive_t)
    cached_s = statistics.median(cached_t)
    tokens = b * (L - 1)
    naive_tps = tokens / naive_s
    cached_tps = tokens / cached_s
    speedup = cached_tps / max(naive_tps, 1e-9)
    sess.close()

    # open-loop join/leave churn through the serving engine: a backlog
    # of short sequences with staggered budgets so slots retire and
    # refill continuously
    slots = 8
    eng_name = "bench_generative"
    model = transformer.TransformerGenerativeModel(
        cfg, src_len, num_slots=slots, max_decode_len=L,
        init_fresh=True, aot_warmup=True)
    policy = serving.DecodePolicy(num_slots=slots, max_decode_len=L,
                                  max_new_tokens=L - 1)
    n_reqs = int(os.environ.get("BENCH_GEN_CHURN_REQS", "32"))
    rng = np.random.RandomState(0)
    prompts = rng.randint(2, cfg.vocab_size,
                          (n_reqs, src_len)).astype(np.int32)
    budgets = [4 + (i * 7) % (L - 4) for i in range(n_reqs)]
    engine = serving.GenerativeEngine(eng_name, model, policy)
    t0 = time.perf_counter()
    futs = [engine.generate(prompts[i], max_new_tokens=budgets[i])
            for i in range(n_reqs)]
    results = [f.result(timeout=600) for f in futs]
    churn_wall = time.perf_counter() - t0
    churn_tokens = sum(len(r["tokens"]) for r in results)
    engine.close()
    fill_cells = monitoring.export().get(
        "/stf/serving/decode_fill", {}).get("cells", {})
    fc = fill_cells.get(eng_name, {})
    fill = (fc.get("sum", 0.0) / fc.get("count", 1)
            if fc.get("count") else 0.0)

    return {
        **_monitoring_info(),
        "metric": "generative_cached_decode_speedup_vs_reforward",
        "value": round(speedup, 2),
        "unit": "x (tokens/sec, cached KV decode / naive re-forward "
                "beam search)",
        "vs_baseline": None,
        "ids_identical": ids_identical,
        "tokens_per_sec_cached": round(cached_tps, 1),
        "tokens_per_sec_naive": round(naive_tps, 1),
        "p50_per_token_ms_cached": round(cached_s / (L - 1) * 1000, 3),
        "p50_per_token_ms_naive": round(naive_s / (L - 1) * 1000, 3),
        "beam_config": f"batch{b}_beam{k}_len{L}",
        "churn_fill_fraction": round(fill, 3),
        "churn_tokens_per_sec": round(churn_tokens / churn_wall, 1),
        "churn_requests": n_reqs,
        "churn_slots": slots,
        "reps": reps,
        "note": ("cached and naive fetch IDENTICAL searches (ids "
                 "compared int-exact); churn row = open-loop backlog "
                 "of staggered-budget sequences over the token-level "
                 "continuous-batching engine, fill from "
                 "/stf/serving/decode_fill"),
    }


def _measure_decode2(platform, device_kind):
    """ISSUE 16: decode throughput II. Two arms:

    (a) SPECULATIVE decoding — target + shrunk draft, both trained on a
        cyclic-copy task (emit the 8-token prompt over and over, so
        their greedy choices agree over a long decode budget and
        acceptance is high) and round-tripped through checkpoints;
        tokens/sec of the speculative engine vs plain cached greedy
        decode on the SAME target checkpoint, token-exact required,
        single-slot latency regime (the draft's fused multi-step
        program and the batched verify re-score amortize the per-step
        dispatch that dominates single-stream decode). Acceptance:
        >=2x.
    (b) SHARED-PREFIX prompt cache — open-loop load where 80% of the
        prompts share a ~75%-length prefix through the paged causal-LM
        engine; median time-to-first-token on a warm prompt cache vs an
        all-unique no-cache baseline round of the same shape (sharing
        starts paying from the second request, so a "cold pass" over
        the shared workload is already mostly warm), plus prefill FLOPs
        avoided. Acceptance: >=3x TTFT reduction on the shared cohort,
        decode fill >= 0.8, page reconcile drift 0.
    """
    import statistics
    import tempfile

    import simple_tensorflow_tpu as stf
    from simple_tensorflow_tpu import serving
    from simple_tensorflow_tpu.framework import cost_model as _cm
    from simple_tensorflow_tpu.models import causal_lm, transformer
    from simple_tensorflow_tpu.platform import monitoring

    tmp = tempfile.mkdtemp(prefix="stf_bench_decode2_")

    # -- (a) speculative vs cached greedy ------------------------------------
    cfg_t = transformer.TransformerConfig(
        vocab_size=64, d_model=64, num_heads=4, d_ff=128, num_layers=2,
        dropout=0.0, max_len=64)
    cfg_d = transformer.TransformerConfig(
        vocab_size=64, d_model=32, num_heads=2, d_ff=64, num_layers=1,
        dropout=0.0, max_len=64)
    src_len, L = 8, 48
    budget = L - 1                 # long decode amortizes prefill
    spec_k = 12
    train_steps = int(os.environ.get("BENCH_DECODE2_TRAIN_STEPS", "1600"))
    tb = 32
    rng = np.random.RandomState(0)

    def _train_copy(cfg, name, lr):
        """Train cyclic copy (tgt = src tiled to the decode budget);
        save a checkpoint; return its path and the final train
        accuracy. The noam schedule scales with d_model**-0.5, but the
        deeper target still diverges at the draft's peak lr — hence
        the per-model lr."""
        stf.reset_default_graph()
        stf.set_random_seed(0)
        m = transformer.transformer_train_model(
            batch_size=tb, src_len=src_len, tgt_len=budget, cfg=cfg,
            learning_rate=lr, warmup_steps=100,
            compute_dtype=stf.float32)
        ckpt = os.path.join(tmp, name)
        reps = (budget + src_len - 1) // src_len
        with stf.Session() as sess:
            sess.run(stf.global_variables_initializer())
            acc = 0.0
            for i in range(train_steps):
                src = rng.randint(2, cfg.vocab_size,
                                  (tb, src_len)).astype(np.int32)
                tgt_out = np.tile(src, (1, reps))[:, :budget]
                tgt_in = np.concatenate(
                    [np.full((tb, 1), cfg.eos_id, np.int32),
                     tgt_out[:, :-1]], axis=1)
                _, acc = sess.run(
                    [m["train_op"], m["accuracy"]],
                    {m["src_ids"]: src, m["tgt_in"]: tgt_in,
                     m["tgt_out"]: tgt_out})
                if acc >= 0.9995 and i > 50:
                    break
            saver = stf.train.Saver()
            saver.save(sess, ckpt)
        return ckpt, float(acc)

    ckpt_t, acc_t = _train_copy(cfg_t, "target", 0.7)
    ckpt_d, acc_d = _train_copy(cfg_d, "draft", 1.0)

    slots = 1
    n_reqs = int(os.environ.get("BENCH_DECODE2_SPEC_REQS", "12"))
    prompts = rng.randint(2, cfg_t.vocab_size,
                          (n_reqs, src_len)).astype(np.int32)

    def _run_arm(model, draft=None, name="d2"):
        policy = serving.DecodePolicy(num_slots=slots,
                                      max_decode_len=L,
                                      max_new_tokens=budget)
        engine = serving.GenerativeEngine(name, model, policy,
                                          draft=draft)
        t0 = time.perf_counter()
        futs = [engine.generate(p, max_new_tokens=budget)
                for p in prompts]
        results = [f.result(timeout=600) for f in futs]
        wall = time.perf_counter() - t0
        stats = engine.statusz_info()
        engine.close()
        toks = [list(r["tokens"]) for r in results]
        return toks, sum(len(t) for t in toks) / wall, stats

    plain_model = transformer.TransformerGenerativeModel(
        cfg_t, src_len, num_slots=slots, max_decode_len=L,
        checkpoint=ckpt_t, aot_warmup=True)
    plain_toks, plain_tps, _ = _run_arm(plain_model, name="d2_plain")

    target = transformer.TransformerGenerativeModel(
        cfg_t, src_len, num_slots=slots, max_decode_len=L,
        checkpoint=ckpt_t, aot_warmup=True, speculative_k=spec_k)
    draft = transformer.TransformerGenerativeModel(
        cfg_d, src_len, num_slots=slots, max_decode_len=L,
        checkpoint=ckpt_d, aot_warmup=True, draft_steps=spec_k - 1)
    spec_toks, spec_tps, spec_stats = _run_arm(target, draft=draft,
                                               name="d2_spec")
    token_exact = bool(plain_toks == spec_toks)
    spec_info = spec_stats.get("speculative", {})
    spec_speedup = spec_tps / max(plain_tps, 1e-9)

    # -- (b) shared-prefix prompt cache --------------------------------------
    # Big enough that per-chunk prefill dominates TTFT over scheduler
    # dispatch (tiny() drowns the cache win in ~1.4ms of queue latency),
    # and prompts sized so the cached span (prompt[:-1]) is page-aligned:
    # every chunk is trie-insertable, no partial tail.
    page_len, pages_per_seq, num_pages = 8, 8, 96
    cfg_c = transformer.TransformerConfig(
        vocab_size=64, d_model=128, num_heads=4, d_ff=256, num_layers=4,
        dropout=0.0, max_len=page_len * pages_per_seq)
    clm_model = causal_lm.CausalLMGenerativeModel(
        cfg_c, page_len=page_len, pages_per_seq=pages_per_seq,
        num_pages=num_pages, max_live=8, init_fresh=True,
        aot_warmup=True, seed=0)
    plen = 41                      # cached = 40 tokens = 5 full pages
    shared = list(rng.randint(2, cfg_c.vocab_size, 32))  # 4 pages, 78%
    n_open = int(os.environ.get("BENCH_DECODE2_PREFIX_REQS", "20"))

    def _mk_prompts(share):
        out = []
        for i in range(n_open):
            if share and i % 5 != 4:   # 80% share the 32-token prefix
                out.append(shared + list(
                    rng.randint(2, cfg_c.vocab_size, plen - len(shared))))
            else:                      # private / no-cache baseline
                out.append(list(rng.randint(2, cfg_c.vocab_size, plen)))
        return out

    base_prompts = _mk_prompts(share=False)
    open_prompts = _mk_prompts(share=True)
    pol = serving.DecodePolicy(num_slots=8,
                               max_decode_len=clm_model.max_seq_len,
                               bucket_sizes=[1, 8], max_new_tokens=6)
    eng = serving.GenerativeEngine("d2_prefix", clm_model, pol)

    def _ttfts(round_prompts):
        """Sequential closed-loop round; per-request seconds to first
        emitted token."""
        out = []
        for p in round_prompts:
            marks = []
            t0 = time.perf_counter()
            fut = eng.generate(p, max_new_tokens=6,
                               on_token=lambda tok, lp, _m=marks:
                               _m.append(time.perf_counter()))
            fut.result(timeout=600)
            out.append(marks[0] - t0)
        return out

    base = _ttfts(base_prompts)            # all-unique: full prefill
    cold = _ttfts(open_prompts)            # first shared pass populates
    pc_after_cold = dict(eng._prefix.statusz_info())
    warm = _ttfts(open_prompts)            # second pass: chunks hit
    pc_stats = dict(eng._prefix.statusz_info())
    drift = eng._prefix.reconcile([])
    eng.close()
    shared_idx = [i for i in range(n_open) if i % 5 != 4]
    base_ttft = statistics.median(base)
    cold_ttft = statistics.median([cold[i] for i in shared_idx])
    warm_ttft = statistics.median([warm[i] for i in shared_idx])
    ttft_reduction = base_ttft / max(warm_ttft, 1e-9)
    hit_tokens = pc_stats["hit_pages"] * page_len
    flops_avoided = _cm.transformer_forward_flops(
        1, hit_tokens, cfg_c.d_model, cfg_c.num_layers, d_ff=cfg_c.d_ff)
    fill_cells = monitoring.export().get(
        "/stf/serving/decode_fill", {}).get("cells", {})
    fc = fill_cells.get("d2_prefix", {})
    fill = (fc.get("sum", 0.0) / fc.get("count", 1)
            if fc.get("count") else 0.0)

    return {
        **_monitoring_info(),
        "metric": "decode2_speculative_speedup_vs_cached_greedy",
        "value": round(spec_speedup, 2),
        "unit": "x (tokens/sec, speculative draft+verify / plain "
                "cached greedy, same target checkpoint)",
        "vs_baseline": None,
        "token_exact": token_exact,
        "spec_tokens_per_sec": round(spec_tps, 1),
        "plain_tokens_per_sec": round(plain_tps, 1),
        "spec_acceptance_rate": round(
            float(spec_info.get("acceptance_rate", 0.0)), 3),
        "spec_proposed_tokens": spec_info.get("proposed_tokens", 0),
        "spec_accepted_tokens": spec_info.get("accepted_tokens", 0),
        "spec_k": spec_k,
        "spec_num_slots": slots,
        "copy_task_accuracy": {"target": round(acc_t, 4),
                               "draft": round(acc_d, 4)},
        "prefix_ttft_reduction": round(ttft_reduction, 2),
        "prefix_ttft_nocache_ms": round(base_ttft * 1000, 3),
        "prefix_ttft_cold_ms": round(cold_ttft * 1000, 3),
        "prefix_ttft_warm_ms": round(warm_ttft * 1000, 3),
        "prefix_cache_stats": pc_stats,
        "prefix_hits_after_cold_pass": pc_after_cold["hit_pages"],
        "prefix_prefill_tokens_avoided": hit_tokens,
        "prefix_prefill_flops_avoided": float(flops_avoided),
        "prefix_fill_fraction": round(fill, 3),
        "prefix_reconcile_drift": int(drift),
        "prefix_workload": (f"{n_open} prompts len {plen}, 80% share a "
                            f"{len(shared)}-token prefix, page_len "
                            f"{page_len}"),
        "note": ("speculative arm: cyclic-copy-trained target+shrunk "
                 "draft through checkpoint round trip, single-slot "
                 "latency regime, emitted streams compared token-exact "
                 "vs plain cached decode; prefix "
                 "arm: warm-cache shared-cohort median TTFT vs an "
                 "all-unique no-cache round of the same shape, "
                 "sequential closed-loop"),
    }


def _measure_decode_tp(platform, device_kind):
    """ISSUE 20: decode-time tensor parallelism. One checkpoint served
    at tp in {1, 4, 8} (head-sharded KV caches over a ``tp`` mesh
    axis, column-parallel projections, one logits all-gather per
    token): tokens/sec + median TTFT per degree, token streams
    compared int-exact against the tp=1 arm, per-device cache bytes
    (~1/tp of replicated: weights replicate, caches shard), and the
    predicted per-token collective bytes next to the bytes harvested
    from the compiled bucket-1 decode program's HLO (acceptance:
    within 25%). Virtual CPU mesh: the tokens/sec column measures
    dispatch overhead, not interconnect speedup — the byte accounting
    is the machine-checkable part."""
    import statistics
    import tempfile

    import jax

    import simple_tensorflow_tpu as stf
    from simple_tensorflow_tpu import parallel, serving
    from simple_tensorflow_tpu.models import transformer
    from simple_tensorflow_tpu.utils import perf as _perf

    tmp = tempfile.mkdtemp(prefix="stf_bench_decode_tp_")
    cfg = transformer.TransformerConfig(
        vocab_size=64, d_model=64, num_heads=8, d_ff=128, num_layers=2,
        dropout=0.0, max_len=64)
    src_len, L = 8, 32
    budget = L - 1
    slots = 2
    n_reqs = int(os.environ.get("BENCH_DECODE_TP_REQS", "6"))
    rng = np.random.RandomState(0)

    stf.reset_default_graph()
    base = transformer.TransformerGenerativeModel(
        cfg, src_len, num_slots=slots, max_decode_len=L,
        init_fresh=True, seed=7, aot_warmup=False)
    ckpt = os.path.join(tmp, "model")
    with base.graph.as_default():
        saver = stf.train.Saver()
        saver.save(base.session, ckpt)
    base.close()

    prompts = rng.randint(2, cfg.vocab_size,
                          (n_reqs, src_len)).astype(np.int32)
    n_dev = len(jax.devices())
    degrees = [t for t in (1, 4, 8)
               if t <= n_dev and cfg.num_heads % t == 0]

    def _arm(tp):
        mesh = parallel.Mesh({"tp": tp}) if tp > 1 else None
        # aot_warmup pre-compiles every bucket program into the plan's
        # AOT cache — the serving configuration, and the only path
        # whose compiled HLO is harvestable for collective bytes
        model = transformer.TransformerGenerativeModel(
            cfg, src_len, num_slots=slots, max_decode_len=L,
            checkpoint=ckpt, aot_warmup=True, mesh=mesh,
            tp=tp if tp > 1 else None)
        harvested = 0.0
        plan, _p = model._decode_plans[min(model._decode_plans)]
        for exe in plan._step.aot_cache.values():
            coll = _perf.collective_bytes_of(exe._compiled)
            harvested = max(harvested, float(coll.get("total", 0.0)))
        info = model.tp_info()
        policy = serving.DecodePolicy(num_slots=slots,
                                      max_decode_len=L,
                                      max_new_tokens=budget)
        engine = serving.GenerativeEngine(f"d_tp{tp}", model, policy)
        futs, firsts = [], []
        t0 = time.perf_counter()
        for p in prompts:
            sub = time.perf_counter()
            first = []
            firsts.append(first)
            futs.append(engine.generate(
                p, max_new_tokens=budget,
                on_token=lambda _t, _lp, _s=sub, _f=first:
                    _f.append(time.perf_counter() - _s)
                    if not _f else None))
        results = [f.result(timeout=600) for f in futs]
        wall = time.perf_counter() - t0
        engine.close()
        model.close()
        toks = sum(len(r["tokens"]) for r in results)
        return {
            "tp": tp,
            "tokens_per_sec": round(toks / wall, 1),
            "ttft_ms": round(statistics.median(
                f[0] for f in firsts if f) * 1000, 3),
            "cache_bytes_per_device": info["cache_bytes_per_device"],
            "cache_bytes_replicated": info["cache_bytes_replicated"],
            "predicted_collective_bytes":
                info["per_token_collective_bytes"],
            "harvested_collective_bytes": harvested,
            "streams": [list(map(int, r["tokens"])) for r in results],
        }

    arms = {t: _arm(t) for t in degrees}
    base_streams = arms[1].pop("streams")
    token_exact = all(arms[t].pop("streams") == base_streams
                      for t in degrees if t > 1)
    top = max(degrees)
    pred = arms[top]["predicted_collective_bytes"]
    harv = arms[top]["harvested_collective_bytes"]
    ratio = (pred / harv) if harv else 0.0
    cache_frac = (arms[top]["cache_bytes_per_device"]
                  / max(arms[top]["cache_bytes_replicated"], 1))
    return {
        **_monitoring_info(),
        "metric": "decode_tp_collective_bytes_predicted_over_harvested",
        "value": round(ratio, 3),
        "unit": "x (predicted / harvested per-token collective bytes, "
                f"tp={top} decode program)",
        "vs_baseline": None,
        "token_exact": token_exact,
        "tp_degrees": degrees,
        "per_degree": {str(t): arms[t] for t in degrees},
        "cache_bytes_per_device_fraction_of_replicated":
            round(cache_frac, 4),
        "note": (f"{n_reqs} prompts, {slots} slots, decode budget "
                 f"{budget}; same checkpoint every arm; streams "
                 "int-exact vs tp=1 required; collective bytes "
                 "harvested from the bucket-1 decode HLO "
                 "(utils.perf.collective_bytes_of)"),
    }


def run_bench_transformer(platform, device_kind):
    batches = [int(x) for x in
               os.environ.get("BENCH_TFMR_BATCH", "16,24").split(",") if x]
    if platform == "cpu":
        batches = batches[:1]
    return _sweep_batches(
        batches, lambda b: _measure_transformer(b, platform, device_kind))


def _stage_feed(mesh, tensor, arr):
    """Pre-stage a feed array on the mesh per the tensor's sharding attr
    (searched or hand-placed; replicated when absent). Shared by the
    resnet_dp and autoshard rows — numpy feeds would re-scatter over the
    mesh every step, an input-pipeline cost, not a sharding cost."""
    import jax

    spec = tensor.op.attrs.get("sharding")
    ns = jax.sharding.NamedSharding(
        mesh.jax_mesh,
        jax.sharding.PartitionSpec(*spec) if spec is not None
        else jax.sharding.PartitionSpec())
    return jax.device_put(arr, ns)


def _measure_resnet_dp(n_devices=8):
    """BASELINE config 3: ResNet data-parallel scaling. No multi-chip
    hardware on this rig, so this measures SHARDING OVERHEAD on a virtual
    n-device CPU mesh at the SAME global batch: efficiency =
    t_unsharded / t_dp — 1.0 means the mesh lowering (psum grads,
    sharded feeds, partitioned program) adds nothing over running the
    identical computation unsharded. On real chips the same code path
    gives true scaling.

    r12 (ISSUE 14): the dp layout is SEARCHED (stf.parallel.auto_shard
    over the train plan — feeds, variable placement, cut points), not
    hand-placed; the pure-JAX control keeps its hand-written specs, so
    the row now reads "searched stf layout vs hand-written JAX"."""
    import jax

    jax.config.update("jax_platforms", "cpu")

    import simple_tensorflow_tpu as stf
    from simple_tensorflow_tpu import parallel
    from simple_tensorflow_tpu.models import resnet

    devices = jax.devices("cpu")
    assert len(devices) >= n_devices, (
        f"need {n_devices} virtual devices, have {len(devices)}")
    per_dev_batch, image = 4, 32
    steps, warmup = 5, 2

    trials = int(os.environ.get("BENCH_DP_TRIALS", "3"))

    def time_model(mesh, batch, collect=None):
        """Compile once, then time the step loop `trials` times; return the
        list of per-step times so the caller can take a median (single
        timings on a shared physical core swung 37% between bench runs).
        bf16 params/activations and pre-staged device feeds to mirror the
        pure-JAX control exactly (numpy feeds would re-scatter over the
        mesh every step — input-pipeline cost, not sharding cost). With a
        mesh, the layout comes from the autoshard SEARCH — no hand specs."""
        import jax.numpy as jnp

        stf.reset_default_graph()
        ctx = mesh if mesh is not None else _NullCtx()
        with ctx:
            m = resnet.resnet50_train_model(
                batch_size=batch, image_size=image, dtype=stf.bfloat16,
                learning_rate=0.1)
            if mesh is not None:
                res = parallel.auto_shard(
                    fetches=[m["train_op"], m["loss"]])
                if collect is not None:
                    collect["autoshard"] = res
            xv, yv = resnet.synthetic_imagenet(batch, image,
                                               dtype=np.float32)
            xd = jnp.asarray(xv, dtype=stf.bfloat16.np_dtype)
            yd = jnp.asarray(yv)
            if mesh is not None:
                xd = _stage_feed(mesh, m["images"], xd)
                yd = _stage_feed(mesh, m["labels"], yd)
            feed = {m["images"]: xd, m["labels"]: yd}
            sess = stf.Session()
            sess.run(stf.global_variables_initializer())
            for _ in range(warmup):
                sess.run(m["train_op"], feed_dict=feed)
            dts = []
            for _ in range(trials):
                sess.run(m["loss"], feed_dict=feed)
                t0 = time.perf_counter()
                for _ in range(steps):
                    sess.run(m["train_op"], feed_dict=feed)
                loss = sess.run(m["loss"], feed_dict=feed)
                dts.append((time.perf_counter() - t0) / (steps + 1))
        assert np.isfinite(np.asarray(loss))
        return dts

    class _NullCtx:
        def __enter__(self):
            return self

        def __exit__(self, *a):
            return False

    def time_pure_jax(shard):
        """Pure-JAX control: the same architecture hand-written in jax,
        jit over the same mesh (sharded) or single-device — measures
        what raw jax+GSPMD pays for the virtual mesh, so the stf ratio
        can be normalized by it."""
        sys.path.insert(0, os.path.join(
            os.path.dirname(os.path.abspath(__file__)), "benchmarks"))
        from _resnet_builder import build_train_step

        train_step, params, x, y = build_train_step(
            per_dev_batch * n_devices, image)
        if shard:
            jmesh = jax.sharding.Mesh(
                np.array(devices[:n_devices]), ("dp",))
            dp = jax.sharding.NamedSharding(
                jmesh, jax.sharding.PartitionSpec("dp"))
            rep = jax.sharding.NamedSharding(
                jmesh, jax.sharding.PartitionSpec())
            x = jax.device_put(x, dp)
            y = jax.device_put(y, dp)
            params = jax.device_put(params, rep)
        step = jax.jit(train_step)
        loss, params = step(params, x, y)
        jax.block_until_ready(loss)
        dts = []
        for _ in range(trials):
            t0 = time.perf_counter()
            for _ in range(steps):
                loss, params = step(params, x, y)
            np.asarray(loss)  # hard sync
            dts.append((time.perf_counter() - t0) / steps)
        return float(np.median(dts))

    # Same-total-work protocol (r5): unsharded batch-32 vs dp-sharded
    # batch-32 for BOTH the stf lowering and a pure-JAX control. On one
    # physical core the partitioned program pays XLA's multi-device
    # emulation cost (serialized partitions + copies); the control pays
    # the identical cost, so efficiency = stf_ratio / jax_ratio isolates
    # what OUR lowering adds over hand-written jax+GSPMD.
    t_single = float(np.median(time_model(None,
                                          per_dev_batch * n_devices)))
    mesh = parallel.Mesh({"dp": n_devices}, devices=devices[:n_devices])
    collected = {}
    t_dp_trials = time_model(mesh, per_dev_batch * n_devices,
                             collect=collected)
    t_dp = float(np.median(t_dp_trials))
    t_jax_single = time_pure_jax(shard=False)
    t_jax_dp = time_pure_jax(shard=True)
    # Emulating 8 devices on one core adds a roughly CONSTANT cost
    # (serialized partitions + inter-"device" copies), so the honest
    # comparison is the ADDED seconds: what sharding costs through the
    # stf lowering vs what the identical sharding costs hand-written
    # (a ratio-of-ratios would punish stf for having the faster
    # unsharded baseline — its one-pass BN VJP beats the naive control).
    stf_added = max(t_dp - t_single, 1e-9)
    jax_added = max(t_jax_dp - t_jax_single, 1e-9)
    efficiency = jax_added / stf_added
    result_extra = {}
    if efficiency > 1.5:
        # stf's sharding cost being 1.5x SMALLER than raw jax's on the
        # same mesh means the bench broke, not that we beat GSPMD
        result_extra["anomalous"] = True
    elif efficiency < 0.8:
        # stf's dp lowering pays >25% more than hand-written jax+GSPMD
        # for the same sharding — a real lowering regression
        result_extra["anomalous"] = True
    return {
        **result_extra,
        "metric": "resnet50_dp8_sharding_efficiency",
        "value": round(float(efficiency), 3),
        "unit": "fraction_of_ideal",
        "vs_baseline": round(float(efficiency), 3),
        "n_devices": n_devices,
        "per_device_batch": per_dev_batch,
        "image_size": image,
        "trials": trials,
        "t_single_s": round(t_single, 4),
        "t_dp_s": round(t_dp, 4),
        "t_dp_trials_s": [round(t, 4) for t in t_dp_trials],
        "t_jax_single_s": round(t_jax_single, 4),
        "t_jax_dp_s": round(t_jax_dp, 4),
        "stf_added_s": round(stf_added, 4),
        "jax_added_s": round(jax_added, 4),
        "layout": "searched (parallel.auto_shard; no hand specs)",
        "autoshard_search_s": round(
            collected["autoshard"].search_seconds, 3)
        if "autoshard" in collected else None,
        "autoshard_feed_specs": {
            k: list(v) for k, v in
            collected["autoshard"].feed_specs.items()}
        if "autoshard" in collected else None,
        "note": ("virtual-mesh check (1 core, same total work, pure-JAX "
                 "control): (t_jax_dp - t_jax_unsharded) / (t_stf_dp - "
                 "t_stf_unsharded) — 1.0 = sharding through the stf "
                 "lowering costs the same seconds as hand-written "
                 "jax+GSPMD on the same mesh"),
        "device": "cpu_virtual_mesh",
    }


def _measure_autoshard(platform, device_kind, n_devices=8):
    """stf.analysis.autoshard row (ISSUE 14): searched vs hand-spec vs
    replicated layouts on the resnet50_dp8 virtual-mesh config.

    Reports (1) efficiency = t_hand / t_searched (>= ~1.0 means the
    searched layout matches-or-beats the hand dp recipe in measured
    seconds), (2) the searched layout's predicted/harvested collective
    byte ratio (the PR 6 validation, now on a CHOSEN layout), and
    (3) the search wall time against the XLA compile it precedes
    (must stay <10% — same budget discipline as the analyzer row)."""
    import jax

    jax.config.update("jax_platforms", "cpu")

    import simple_tensorflow_tpu as stf
    from simple_tensorflow_tpu import parallel
    from simple_tensorflow_tpu.models import resnet

    devices = jax.devices("cpu")
    assert len(devices) >= n_devices, (
        f"need {n_devices} virtual devices, have {len(devices)}")
    per_dev_batch, image = 4, 32
    batch = per_dev_batch * n_devices
    steps, warmup = 4, 1
    trials = int(os.environ.get("BENCH_AUTOSHARD_TRIALS", "2"))

    def run_layout(layout):
        import jax.numpy as jnp

        stf.reset_default_graph()
        mesh = parallel.Mesh({"dp": n_devices},
                             devices=devices[:n_devices])
        out = {}
        with mesh:
            m = resnet.resnet50_train_model(
                batch_size=batch, image_size=image, dtype=stf.bfloat16,
                learning_rate=0.1)
            if layout == "hand":
                parallel.shard_feed(m["images"], "dp")
                parallel.shard_feed(m["labels"], "dp")
                for v in stf.global_variables():
                    if v.sharding is None:
                        v.set_sharding(parallel.P())
            elif layout == "searched":
                res = parallel.auto_shard(
                    fetches=[m["train_op"], m["loss"]])
                out["search_seconds"] = res.search_seconds
                out["candidates"] = res.candidates_priced
                out["predicted_bytes"] = res.predicted[
                    "collective_bytes"]
                out["feed_specs"] = {k: list(v) for k, v in
                                     res.feed_specs.items()}
            xv, yv = resnet.synthetic_imagenet(batch, image,
                                               dtype=np.float32)
            xd = jnp.asarray(xv, dtype=stf.bfloat16.np_dtype)
            yd = jnp.asarray(yv)

            xd = _stage_feed(mesh, m["images"], xd)
            yd = _stage_feed(mesh, m["labels"], yd)
            feed = {m["images"]: xd, m["labels"]: yd}
            sess = stf.Session()
            sess.run(stf.global_variables_initializer())
            t0 = time.perf_counter()
            opts = md = None
            if layout == "searched":
                opts = stf.RunOptions(
                    trace_level=stf.RunOptions.SOFTWARE_TRACE)
                md = stf.RunMetadata()
            sess.run(m["train_op"], feed_dict=feed, options=opts,
                     run_metadata=md)
            out["compile_s"] = time.perf_counter() - t0
            if md is not None:
                harvested = md.cost_graph.get("collective_bytes", {})
                out["harvested_bytes"] = float(
                    harvested.get("total", 0.0))
                reps = [s for s in sess._cache.values()
                        if s.join_sharding() is not None]
                if reps:
                    out["analyzer_predicted_bytes"] = \
                        reps[-1].sharding_report \
                        .total_collective_bytes()
            for _ in range(warmup):
                sess.run(m["train_op"], feed_dict=feed)
            dts = []
            for _ in range(trials):
                sess.run(m["loss"], feed_dict=feed)
                t0 = time.perf_counter()
                for _ in range(steps):
                    sess.run(m["train_op"], feed_dict=feed)
                loss = sess.run(m["loss"], feed_dict=feed)
                dts.append((time.perf_counter() - t0) / (steps + 1))
            sess.close()
        assert np.isfinite(np.asarray(loss))
        out["step_s"] = float(np.median(dts))
        return out

    replicated = run_layout("replicated")
    hand = run_layout("hand")
    searched = run_layout("searched")

    efficiency = hand["step_s"] / max(searched["step_s"], 1e-9)
    pred = searched.get("analyzer_predicted_bytes") or \
        searched.get("predicted_bytes", 0.0)
    harv = searched.get("harvested_bytes", 0.0)
    ratio = (pred / harv) if harv else None
    search_frac = searched.get("search_seconds", 0.0) / max(
        searched["compile_s"], 1e-9)
    return {
        "metric": "autoshard_searched_vs_hand_efficiency",
        "value": round(float(efficiency), 3),
        "unit": "x (hand-spec step time / searched-layout step time)",
        "vs_baseline": round(float(efficiency), 3),
        "within_budget": bool(search_frac < 0.10),
        "t_searched_s": round(searched["step_s"], 4),
        "t_hand_s": round(hand["step_s"], 4),
        "t_replicated_s": round(replicated["step_s"], 4),
        "search_wall_s": round(searched.get("search_seconds", 0.0), 3),
        "search_candidates": searched.get("candidates"),
        "compile_s": round(searched["compile_s"], 2),
        "search_over_compile": round(search_frac, 4),
        "predicted_collective_bytes": round(pred),
        "harvested_collective_bytes": round(harv),
        "predicted_over_harvested": (round(ratio, 4)
                                     if ratio is not None else None),
        "within_5pct": (bool(abs(ratio - 1.0) <= 0.05)
                        if ratio is not None else None),
        "searched_feed_specs": searched.get("feed_specs"),
        "note": ("resnet50 dp8 virtual mesh: searched "
                 "(parallel.auto_shard, no hand specs) vs hand dp "
                 "recipe vs no-spec replicated-on-dev0 baseline; "
                 "predicted/harvested on the SEARCHED layout"),
        "device": "cpu_virtual_mesh",
    }


def _measure_embedding(platform, device_kind, n_devices=8):
    """Sharded-embedding row (ISSUE 19): fused gather/scatter-add +
    dedup-before-lookup vs the naive one-hot contraction, on a Zipf
    (skewed) id stream against a vocab-sharded table on the ep=8
    virtual mesh.

    The table is sized to 4x the per-device byte budget this row
    declares, so replication is off the table (the layout the
    lint/embedding-replicated-table gate rejects) and the comparison is
    between the two ways of *reaching* a sharded table: one-hot matmul
    + all-reduce vs the fused route (ids all-to-all, owner-local
    gather, rows all-to-all back). Bar: fused+dedup >= 3x naive.
    Also validates the analyzer's priced all-to-all bytes against the
    bytes harvested from the compiled HLO (within 25%), and writes the
    full row to artifacts/bench_embedding_r19.json."""
    import jax

    jax.config.update("jax_platforms", "cpu")

    import jax.numpy as jnp  # noqa: F401

    import simple_tensorflow_tpu as stf
    from simple_tensorflow_tpu import parallel

    devices = jax.devices("cpu")
    assert len(devices) >= n_devices, (
        f"need {n_devices} virtual devices, have {len(devices)}")
    dim = 64
    n_ids = 2048                       # flat zipf id stream per step
    steps, warmup = 3, 1
    trials = int(os.environ.get("BENCH_EMBEDDING_TRIALS", "2"))
    vocab_sweep = (1 << 13, 1 << 15)   # 2 MiB and 8 MiB f32 tables
    lr = 0.01

    rng = np.random.RandomState(19)

    def zipf_ids(vocab):
        return np.minimum(rng.zipf(1.3, n_ids) - 1,
                          vocab - 1).astype(np.int32)

    def run_config(vocab, path, dedup, trace=False):
        stf.reset_default_graph()
        mesh = parallel.Mesh({"ep": n_devices},
                             devices=devices[:n_devices])
        out = {}
        with mesh:
            with parallel.shard_variables_along("ep", min_size=1,
                                                dim=0):
                table = stf.get_variable(
                    "bench/table", [vocab, dim],
                    initializer=stf.random_uniform_initializer(
                        -0.05, 0.05, seed=7))
            ids_ph = stf.placeholder(stf.int32, [n_ids], name="ids")
            if path == "fused":
                rows = stf.nn.embedding_lookup_fused(table, ids_ph,
                                                     dedup=dedup)
            else:
                # the textbook SPMD lowering: materialize the one-hot
                # and contract over the sharded vocab dim (partial
                # matmuls + an all-reduce of the (B, D) result)
                oh = stf.one_hot(ids_ph, vocab, dtype=stf.float32)
                rows = stf.matmul(oh, table)
            loss = stf.reduce_sum(stf.multiply(rows, rows))
            train = stf.train.GradientDescentOptimizer(lr) \
                .minimize(loss)
            ids = zipf_ids(vocab)
            feed = {ids_ph: ids}
            sess = stf.Session()
            sess.run(stf.global_variables_initializer())
            opts = md = None
            if trace:
                opts = stf.RunOptions(
                    trace_level=stf.RunOptions.SOFTWARE_TRACE)
                md = stf.RunMetadata()
            t0 = time.perf_counter()
            sess.run(train, feed_dict=feed, options=opts,
                     run_metadata=md)
            out["compile_s"] = time.perf_counter() - t0
            if md is not None:
                coll = md.cost_graph.get("collective_bytes", {})
                out["harvested_a2a_bytes"] = float(
                    coll.get("all-to-all", 0.0))
                pred = md.cost_graph.get("predicted_collectives", {})
                out["predicted_a2a_bytes"] = float(
                    pred.get("bytes_by_kind", {})
                    .get("all-to-all", 0.0))
            for _ in range(warmup):
                sess.run(train, feed_dict=feed)
            dts = []
            for _ in range(trials):
                t0 = time.perf_counter()
                for _ in range(steps):
                    sess.run(train, feed_dict=feed)
                dts.append((time.perf_counter() - t0) / steps)
            loss_v = sess.run(loss, feed_dict=feed)
            sess.close()
        assert np.isfinite(loss_v)
        out["step_s"] = float(np.median(dts))
        out["lookups_per_sec"] = n_ids / out["step_s"]
        out["unique_frac"] = float(np.unique(ids).size) / n_ids
        return out

    sweep = {}
    for vocab in vocab_sweep:
        table_mb = vocab * dim * 4 / 2**20
        sweep[vocab] = {
            "table_bytes": vocab * dim * 4,
            "table_mb": round(table_mb, 1),
            # the budget this table is 4x over: replication infeasible
            "device_budget_bytes": vocab * dim * 4 // 4,
            "fused_dedup": run_config(vocab, "fused", True,
                                      trace=(vocab == vocab_sweep[-1])),
            "fused_nodedup": run_config(vocab, "fused", False),
            "naive_onehot": run_config(vocab, "onehot", False),
        }

    head = sweep[vocab_sweep[-1]]
    fused = head["fused_dedup"]
    naive = head["naive_onehot"]
    speedup = naive["step_s"] / max(fused["step_s"], 1e-9)
    pred = fused.get("predicted_a2a_bytes", 0.0)
    harv = fused.get("harvested_a2a_bytes", 0.0)
    ratio = (pred / harv) if harv else None
    result = {
        "metric": "embedding_fused_dedup_speedup_vs_onehot",
        "value": round(float(speedup), 2),
        "unit": ("x (step time, naive one-hot+all-reduce / "
                 "fused+dedup, zipf ids, ep8 vocab-sharded table)"),
        "vs_baseline": round(float(speedup), 2),
        "meets_3x_bar": bool(speedup >= 3.0),
        "lookups_per_sec_fused_dedup": round(
            fused["lookups_per_sec"]),
        "lookups_per_sec_naive": round(naive["lookups_per_sec"]),
        "dedup_unique_frac": round(fused["unique_frac"], 4),
        "predicted_a2a_bytes": round(pred),
        "harvested_a2a_bytes": round(harv),
        "predicted_over_harvested": (round(ratio, 4)
                                     if ratio is not None else None),
        "within_25pct": (bool(abs(ratio - 1.0) <= 0.25)
                         if ratio is not None else None),
        "table_bytes_over_device_budget": 4.0,
        "sweep": {
            str(v): {
                "table_mb": sweep[v]["table_mb"],
                "fused_dedup_step_s": round(
                    sweep[v]["fused_dedup"]["step_s"], 5),
                "fused_nodedup_step_s": round(
                    sweep[v]["fused_nodedup"]["step_s"], 5),
                "naive_onehot_step_s": round(
                    sweep[v]["naive_onehot"]["step_s"], 5),
            } for v in vocab_sweep},
        "note": ("ep8 virtual mesh; fused = EmbeddingLookupFused "
                 "(dedup-before-lookup, ids+rows all-to-all, device "
                 "scatter-add backward), naive = one_hot @ table; "
                 "predicted bytes from the sharding analyzer's fused "
                 "rule, harvested from the compiled HLO "
                 "(utils.perf.collective_bytes_of)"),
        "device": "cpu_virtual_mesh",
    }
    try:
        art_dir = os.path.join(
            os.path.dirname(os.path.abspath(__file__)), "artifacts")
        os.makedirs(art_dir, exist_ok=True)
        with open(os.path.join(art_dir, "bench_embedding_r19.json"),
                  "w") as f:
            json.dump(result, f, indent=1)
    except OSError:
        pass
    return result


_VIRTUAL_MESH_ROWS = ("resnet_dp", "sharding_analysis", "autoshard",
                      "embedding", "decode_tp")


def child_main():
    """Runs the actual bench; prints the JSON line itself on success.
    Takes the device JAX gives it: the TPU, or — for the virtual-mesh
    rows only — the CPU mesh the parent set up. Anything else fails."""
    import jax

    from simple_tensorflow_tpu.compiler import aot

    model = os.environ.get("BENCH_MODEL", "resnet")
    dev = jax.devices()[0]
    platform, kind = dev.platform, dev.device_kind
    want = "cpu" if model in _VIRTUAL_MESH_ROWS else "tpu"
    if platform != want:
        sys.exit(f"bench row {model!r} needs a {want} backend, "
                 f"found {platform!r}")
    # compiles cost 30-120 s per program; persist them so repeat bench
    # runs spend their timeout measuring, not compiling
    aot.enable_persistent_cache()
    if model == "bert":
        result = run_bench_bert(platform, kind)
    elif model == "mnist":
        result = _measure_mnist(platform, kind)
    elif model == "transformer":
        result = run_bench_transformer(platform, kind)
    elif model == "resnet_dp":
        result = _measure_resnet_dp()
    elif model == "graph_opt":
        result = _measure_graph_opt(platform, kind)
    elif model == "analysis":
        result = _measure_analysis(platform, kind)
    elif model == "sharding_analysis":
        result = _measure_sharding_analysis(platform, kind)
    elif model == "autoshard":
        result = _measure_autoshard(platform, kind)
    elif model == "loop_fusion":
        result = _measure_loop_fusion(platform, kind)
    elif model == "numerics":
        result = _measure_numerics(platform, kind)
    elif model == "input_pipeline":
        result = _measure_input_pipeline(platform, kind)
    elif model == "serving":
        result = _measure_serving(platform, kind)
    elif model == "telemetry":
        result = _measure_telemetry(platform, kind)
    elif model == "sync":
        result = _measure_sync(platform, kind)
    elif model == "memory":
        result = _measure_memory(platform, kind)
    elif model == "checkpoint":
        result = _measure_checkpoint(platform, kind)
    elif model == "kernel_tier":
        result = _measure_kernel_tier(platform, kind)
    elif model == "generative":
        result = _measure_generative(platform, kind)
    elif model == "decode2":
        result = _measure_decode2(platform, kind)
    elif model == "decode_tp":
        result = _measure_decode_tp(platform, kind)
    elif model == "embedding":
        result = _measure_embedding(platform, kind)
    else:
        result = run_bench(platform, kind)
    emit(result)


def _spawn_child(env, timeout_s):
    """Run bench.py --child; return (parsed JSON line, None) or
    (None, what went wrong)."""
    try:
        out = subprocess.run(
            [sys.executable, os.path.abspath(__file__), "--child"],
            env=env, capture_output=True, text=True, timeout=timeout_s)
    except subprocess.TimeoutExpired:
        return None, "timeout"
    if out.stderr:
        sys.stderr.write(out.stderr[-4000:])
    if out.returncode == 0:
        for line in reversed(out.stdout.strip().splitlines()):
            try:
                parsed = json.loads(line)
                if isinstance(parsed, dict) and "metric" in parsed:
                    return parsed, None
            except (json.JSONDecodeError, ValueError):
                continue
    return None, f"rc={out.returncode}, no JSON line"


def _run_model(model):
    """Run one model's bench in a killable child. Returns
    (row, None) or (None, error): a failed row is not a row."""
    name, unit = _METRIC_NAMES[model]
    if model == "warm_start":
        # ISSUE 5 satellite: two sequential child PROCESSES sharing one
        # persistent compile cache. The row is the second process's
        # warmup_plus_compile_s — the restart cost that used to be paid
        # in full every process. The cache must start EMPTY, so it is a
        # fresh directory under the cache root, handed to the children
        # the way any outside caller places the cache.
        import shutil

        root = os.environ.get("JAX_COMPILATION_CACHE_DIR") or os.path.join(
            os.path.dirname(os.path.abspath(__file__)), ".jax_cache")
        cache_dir = os.path.join(root, f"warm_start_{os.getpid()}")
        env = dict(os.environ)
        env["BENCH_MODEL"] = "mnist"
        env["JAX_COMPILATION_CACHE_DIR"] = cache_dir
        timeout_s = int(os.environ.get("BENCH_TIMEOUT", "600"))
        try:
            cold, err_c = _spawn_child(env, timeout_s)
            warm, err_w = _spawn_child(env, timeout_s)
        finally:
            shutil.rmtree(cache_dir, ignore_errors=True)
        if cold is None or warm is None:
            return None, f"warm_start_run_failed: cold={err_c} warm={err_w}"
        cold_s = float(cold.get("warmup_plus_compile_s", 0.0))
        warm_s = float(warm.get("warmup_plus_compile_s", 0.0))
        return {
            "metric": name,
            "value": warm_s,
            "unit": unit,
            "vs_baseline": None,
            "cold_warmup_plus_compile_s": cold_s,
            "warm_warmup_plus_compile_s": warm_s,
            "compile_cache_speedup": round(cold_s / max(warm_s, 1e-9), 2),
            "note": ("same mnist child twice sharing one fresh "
                     "persistent compile cache; the second process "
                     "disk-hits its XLA compiles "
                     "(compiler.aot.enable_persistent_cache)"),
        }, None
    if model in _VIRTUAL_MESH_ROWS:
        # virtual-mesh rows: always a CPU-mesh child by design (their
        # ``device`` field says cpu_virtual_mesh)
        env = dict(os.environ)
        env["JAX_PLATFORMS"] = "cpu"
        env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "")
                            + " --xla_force_host_platform_device_count=8"
                            ).strip()
        env["BENCH_MODEL"] = model
        # the pure-JAX control (r5) adds two timed configs to this child
        result, err = _spawn_child(
            env, int(os.environ.get("BENCH_DP_TIMEOUT", "1800")))
        if result is None:
            return None, f"{model}_run_failed: {err}"
        result.setdefault("device", "cpu_virtual_mesh")
        return result, None
    # per-model TPU time budgets: the headline metrics (resnet, bert) get
    # the full window; secondary configs are bounded so one slow compile
    # cannot eat the driver's whole bench budget
    # resnet runs up to 5 compile+measure cycles (2 batch + 3 variants)
    default_timeout = {"resnet": "2400", "bert": "1500",
                       "transformer": "1200", "mnist": "300",
                       "analysis": "600", "sharding_analysis": "900",
                       "loop_fusion": "900",
                       "numerics": "900",
                       "input_pipeline": "600",
                       "serving": "900",
                       "telemetry": "900",
                       "sync": "900",
                       "memory": "900",
                       "checkpoint": "600",
                       "generative": "1200",
                       "decode2": "1500"}.get(
        model, "900")
    env = dict(os.environ)
    env["BENCH_MODEL"] = model
    result, err = _spawn_child(
        env, int(os.environ.get("BENCH_TIMEOUT", default_timeout)))
    if result is None:
        return None, f"{model}_tpu_run_failed: {err}"
    return result, None


_METRIC_NAMES = {
    "resnet": ("resnet50_images_per_sec_per_chip", "images/sec/chip"),
    "bert": ("bert_base_tokens_per_sec_per_chip", "tokens/sec/chip"),
    "mnist": ("mnist_softmax_examples_per_sec", "examples/sec"),
    "transformer": ("transformer_big_tokens_per_sec_per_chip",
                    "tokens/sec/chip"),
    "resnet_dp": ("resnet50_dp8_sharding_efficiency", "fraction_of_ideal"),
    "graph_opt": ("graph_opt_cond_scan_step_ms", "ms/step (optimized)"),
    "analysis": ("analysis_overhead_frac",
                 "fraction of plan time (prune+optimize+lower+analysis)"),
    "sharding_analysis": (
        "sharding_analysis_overhead_frac",
        "fraction of plan time (prune+optimize+lower+analysis)"),
    "autoshard": (
        "autoshard_searched_vs_hand_efficiency",
        "x (hand-spec step time / searched-layout step time)"),
    "loop_fusion": ("loop_fusion_bert_amortization_n64_vs_n1",
                    "x (measured_over_predicted improvement)"),
    "numerics": ("numerics_plane_overhead_pct_fused_n64",
                 "% overhead (numerics metrics plane ON vs OFF, "
                 "fused N=64)"),
    "input_pipeline": ("input_pipeline_records_per_sec", "records/sec"),
    "serving": ("serving_qps_speedup_batched_vs_batch1",
                "x (QPS, 16 concurrent closed-loop clients)"),
    "telemetry": ("telemetry_overhead_frac",
                  "fraction (worst of serving QPS loss / train "
                  "step-time growth, telemetry ON vs OFF)"),
    "sync": ("sync_witness_overhead_frac",
             "fraction (worst of serving/fused-train accounted "
             "overhead, lock witness ON vs OFF)"),
    "memory": ("memory_plane_overhead_frac",
               "fraction (worst of serving/train accounted overhead, "
               "telemetry plane + HBM ledger fully ON)"),
    "checkpoint": ("checkpoint_async_stall_speedup_vs_blocking",
                   "x (blocking Saver.save stall / async manager.save "
                   "stall)"),
    "kernel_tier": ("kernel_tier_fused_optimizer_tail_speedup",
                    "x (per-variable assign tail / fused update, BERT "
                    "small-step config)"),
    "generative": ("generative_cached_decode_speedup_vs_reforward",
                   "x (tokens/sec, cached KV decode / naive re-forward "
                   "beam search)"),
    "decode2": ("decode2_speculative_speedup_vs_cached_greedy",
                "x (tokens/sec, speculative draft+verify / plain "
                "cached greedy, same target checkpoint)"),
    "decode_tp": (
        "decode_tp_collective_bytes_predicted_over_harvested",
        "x (predicted / harvested per-token collective bytes, tp "
        "decode program)"),
    "warm_start": ("warm_start_warmup_plus_compile_s",
                   "s (second process, shared persistent compile cache)"),
    "embedding": ("embedding_fused_dedup_speedup_vs_onehot",
                  "x (step time, naive one-hot+all-reduce / "
                  "fused+dedup, zipf ids, ep8 vocab-sharded table)"),
}


def main():
    """Parent: run each selected row in a killable child, one at a time,
    print the rows that succeeded, and return the failures (the process
    exits non-zero when there are any). ResNet (the driver's primary)
    prints first."""
    # BENCH_MODELS: comma list to restrict (e.g. "resnet,bert" for a
    # quick headline pass when chip time is scarce); tokens stripped and
    # validated
    selected = []
    for tok in os.environ.get(
            "BENCH_MODELS",
            "resnet,bert,transformer,mnist,resnet_dp,graph_opt,analysis,"
            "sharding_analysis,autoshard,loop_fusion,numerics,"
            "input_pipeline,serving,"
            "telemetry,sync,memory,checkpoint,kernel_tier,generative,"
            "decode2,decode_tp,warm_start,embedding").split(","):
        tok = tok.strip()
        if not tok:
            continue
        if tok not in _METRIC_NAMES:
            sys.exit(f"BENCH_MODELS: unknown model {tok!r}; choices: "
                     f"{sorted(_METRIC_NAMES)}")
        selected.append(tok)
    if not selected:
        sys.exit("BENCH_MODELS selected nothing")
    failures = []
    for model in selected:
        result, err = _run_model(model)
        if result is None:
            print(f"bench: {err}", file=sys.stderr)
            failures.append(err)
        else:
            emit(result)
    return failures


if __name__ == "__main__":
    if "--child" in sys.argv:
        child_main()
    else:
        sys.exit(1 if main() else 0)
