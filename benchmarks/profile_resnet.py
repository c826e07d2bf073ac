#!/usr/bin/env python
"""Diagnose the ResNet-50 step: where do the 97 ms go?

Round-3 perf work. Produces:
  - compiled cost analysis (FLOPs, bytes) of the session's jitted step
  - a scan of the optimized HLO for f32 convolutions (MXU rate killers)
  - timing: session.run loop vs direct jitted-call loop (isolates Python
    dispatch) vs a hand-written pure-JAX ResNet step (isolates lowering)
  - optionally a jax.profiler trace under artifacts/

Usage: python benchmarks/profile_resnet.py [--trace] [--batch N]
"""

import argparse
import json
import os
import re
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def build_session_step(batch, image_size):
    import simple_tensorflow_tpu as stf
    from simple_tensorflow_tpu.models import resnet
    import jax.numpy as jnp

    stf.reset_default_graph()
    m = resnet.resnet50_train_model(batch_size=batch, image_size=image_size,
                                    dtype=stf.bfloat16, learning_rate=0.1)
    images, labels = resnet.synthetic_imagenet(batch, image_size)
    images_dev = jnp.asarray(images, dtype=stf.bfloat16.np_dtype)
    labels_dev = jnp.asarray(labels)
    feed = {m["images"]: images_dev, m["labels"]: labels_dev}
    sess = stf.Session()
    sess.run(stf.global_variables_initializer())
    sess.run(m["train_op"], feed_dict=feed)  # compile + cache
    return sess, m, feed


def analyze_hlo(sess, m, feed):
    """Lower the cached step and scan optimized HLO."""
    step = max((v for v in sess._cache.values() if v.has_device_stage),
               key=lambda s: len(s.device_ops))
    feeds = sess._normalize_feeds(feed)
    feed_args = {t.name: feeds[t] for t in step.feed_tensors}
    state = dict(sess._variable_store.values)
    lowered = step.jitted.lower(state, feed_args, sess._base_key,
                                np.uint32(999))
    compiled = lowered.compile()
    cost = compiled.cost_analysis()
    if isinstance(cost, list):
        cost = cost[0]
    hlo = compiled.as_text()

    convs = re.findall(r"(\w+)\[[\d,]+\]\{[\d,]+\} convolution", hlo)
    conv_dtypes = {}
    for d in convs:
        conv_dtypes[d] = conv_dtypes.get(d, 0) + 1
    dots = re.findall(r"(\w+)\[[\d,]+\]\{[\d,]+\} dot", hlo)
    dot_dtypes = {}
    for d in dots:
        dot_dtypes[d] = dot_dtypes.get(d, 0) + 1
    n_fusions = hlo.count(" fusion(")
    n_convert = len(re.findall(r"convert\(", hlo))
    return {
        "flops": cost.get("flops"),
        "bytes_accessed": cost.get("bytes accessed"),
        "transcendentals": cost.get("transcendentals"),
        "conv_dtypes": conv_dtypes,
        "dot_dtypes": dot_dtypes,
        "n_fusions": n_fusions,
        "n_converts": n_convert,
        "hlo_lines": hlo.count("\n"),
    }, hlo


def time_session_loop(sess, m, feed, steps):
    t0 = time.perf_counter()
    for _ in range(steps):
        sess.run(m["train_op"], feed_dict=feed)
    sess.run(m["loss"], feed_dict=feed)
    return (time.perf_counter() - t0) / (steps + 1)


def time_direct_loop(sess, m, feed, steps):
    """Call the cached jitted fn directly — no Session dispatch at all."""
    import jax

    step = max((v for v in sess._cache.values() if v.has_device_stage),
               key=lambda s: len(s.device_ops))
    feeds = sess._normalize_feeds(feed)
    feed_args = {t.name: feeds[t] for t in step.feed_tensors}
    state = dict(sess._variable_store.values)
    rng_args = (sess._base_key, np.uint32(12345))
    # warm
    _, state, _ = step.jitted(dict(state), feed_args, *rng_args)
    jax.block_until_ready(state)
    t0 = time.perf_counter()
    for i in range(steps):
        _, state, _ = step.jitted(dict(state), feed_args, *rng_args)
    jax.block_until_ready(state)
    dt = (time.perf_counter() - t0) / steps
    # restore store (we donated copies; the session's own arrays were donated
    # away on the very first call, so re-commit the final state)
    sess._variable_store.values = dict(state)
    return dt


def time_pure_jax(batch, image_size, steps):
    """Hand-written minimal ResNet-50 fwd+bwd+SGD in raw JAX: the XLA
    ceiling for this model shape, independent of the stf lowering."""
    import jax

    from benchmarks._resnet_builder import build_train_step

    train_step, params, x, y = build_train_step(batch, image_size,
                                                bn_mode="bf16_apply")
    loss, params = train_step(params, x, y)
    jax.block_until_ready(loss)
    t0 = time.perf_counter()
    for _ in range(steps):
        loss, params = train_step(params, x, y)
    jax.block_until_ready(loss)
    return (time.perf_counter() - t0) / steps


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--batch", type=int, default=256)
    ap.add_argument("--image", type=int, default=224)
    ap.add_argument("--steps", type=int, default=20)
    ap.add_argument("--trace", action="store_true")
    ap.add_argument("--skip-pure", action="store_true")
    ap.add_argument("--dump-hlo", default=None)
    args = ap.parse_args()

    import jax

    from bench import detect_peak_flops

    dev = jax.devices()[0]
    peak = detect_peak_flops(getattr(dev, "device_kind", ""), dev.platform)
    out = {"device": str(dev), "batch": args.batch}

    print("== building session step ==", file=sys.stderr)
    sess, m, feed = build_session_step(args.batch, args.image)

    print("== HLO analysis ==", file=sys.stderr)
    stats, hlo = analyze_hlo(sess, m, feed)
    out["hlo"] = stats
    if args.dump_hlo:
        with open(args.dump_hlo, "w") as f:
            f.write(hlo)

    print("== session.run loop ==", file=sys.stderr)
    out["session_sec_per_step"] = time_session_loop(sess, m, feed, args.steps)

    print("== direct jitted loop ==", file=sys.stderr)
    out["direct_sec_per_step"] = time_direct_loop(sess, m, feed, args.steps)

    if args.trace:
        import jax.profiler

        with jax.profiler.trace("/root/repo/artifacts/resnet_trace"):
            for _ in range(3):
                sess.run(m["train_op"], feed_dict=feed)
            sess.run(m["loss"], feed_dict=feed)
        out["trace_dir"] = "/root/repo/artifacts/resnet_trace"

    if not args.skip_pure:
        print("== pure-JAX reference step ==", file=sys.stderr)
        out["pure_jax_sec_per_step"] = time_pure_jax(
            args.batch, args.image, args.steps)

    flops = 3.0 * 4.089e9 * (args.image / 224.0) ** 2 * args.batch
    for k in ("session_sec_per_step", "direct_sec_per_step",
              "pure_jax_sec_per_step"):
        if k in out:
            out[k.replace("sec_per_step", "mfu")] = round(
                flops / out[k] / peak, 4)
            out[k] = round(out[k], 5)
    print(json.dumps(out, indent=2))


if __name__ == "__main__":
    main()
