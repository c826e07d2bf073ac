#!/usr/bin/env python
"""Diagnose the BERT-base pretraining step (the second BASELINE
metric). Reports XLA cost analysis (FLOPs, bytes accessed), scans the
optimized HLO for full-size f32 tensors / unfused passes, and times the
step with a host fetch as the sync.

Usage: python benchmarks/profile_bert.py [--batch N] [--seq N] [--dump-hlo F]
"""

import argparse
import json
import os
import re
import sys
import time
from collections import Counter

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def build(batch, seq_len):
    import jax.numpy as jnp

    import simple_tensorflow_tpu as stf
    from simple_tensorflow_tpu.models import bert

    cfg = bert.BertConfig.base()
    max_pred = max(1, int(seq_len * 0.15))
    stf.reset_default_graph()
    m = bert.bert_pretrain_model(batch_size=batch, seq_len=seq_len,
                                 max_predictions=max_pred, cfg=cfg,
                                 compute_dtype=stf.bfloat16,
                                 use_input_mask=True)
    batch_np = bert.synthetic_pretrain_batch(batch, seq_len, max_pred,
                                             vocab_size=cfg.vocab_size)
    batch_np["input_mask"] = np.ones((batch, seq_len), np.int32)
    feed = {m[k]: jnp.asarray(v) for k, v in batch_np.items()}
    sess = stf.Session()
    sess.run(stf.global_variables_initializer())
    sess.run(m["train_op"], feed_dict=feed)
    # warm the loss-only fetch too: time_step uses it as the sync barrier,
    # and its first use compiles a separate program (30-60 s remote AOT)
    sess.run(m["loss"], feed_dict=feed)
    return sess, m, feed, cfg


def analyze(sess, m, feed):
    step = max((v for v in sess._cache.values() if v.has_device_stage),
               key=lambda s: len(s.device_ops))
    feeds = sess._normalize_feeds(feed)
    feed_args = {t.name: feeds[t] for t in step.feed_tensors}
    state = dict(sess._variable_store.values)
    compiled = step.jitted.lower(state, feed_args, sess._base_key,
                                 np.uint32(999)).compile()
    cost = compiled.cost_analysis()
    if isinstance(cost, list):
        cost = cost[0]
    hlo = compiled.as_text()
    # top-level buffer writes by (dtype, MB bucket)
    mm = re.search(r"\nENTRY [^{]+\{(.*)", hlo, re.S)
    writes = Counter()
    for line in mm.group(1).split("\n"):
        lm = re.match(
            r"\s+(?:ROOT )?%?[\w.-]+ = \(?([a-z0-9]+)\[([0-9,]*)\]", line)
        if not lm:
            continue
        dt, dims = lm.groups()
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        sz = n * {"f32": 4, "bf16": 2, "s32": 4, "pred": 1}.get(dt, 4)
        if sz >= 8_000_000:
            writes[f"{dt}[{dims}]"] += sz
    return {
        "flops_T": round(cost.get("flops", 0) / 1e12, 3),
        "bytes_gb": round(cost.get("bytes accessed", 0) / 1e9, 2),
        "top_writes": [(k, round(v / 1e9, 2)) for k, v in
                       writes.most_common(12)],
    }, hlo, step


def time_step(sess, m, feed, steps=15):
    t0 = time.perf_counter()
    for _ in range(steps):
        sess.run(m["train_op"], feed_dict=feed)
    _ = sess.run(m["loss"], feed_dict=feed)  # hard sync via host fetch
    return (time.perf_counter() - t0) / (steps + 1)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--batch", type=int, default=24)
    ap.add_argument("--seq", type=int, default=512)
    ap.add_argument("--steps", type=int, default=15)
    ap.add_argument("--dump-hlo", default=None)
    args = ap.parse_args()

    from simple_tensorflow_tpu.models import bert

    from bench import detect_peak_flops
    import jax

    dev = jax.devices()[0]
    peak = detect_peak_flops(getattr(dev, "device_kind", ""), dev.platform)

    sess, m, feed, cfg = build(args.batch, args.seq)
    stats, hlo, step = analyze(sess, m, feed)
    if args.dump_hlo:
        with open(args.dump_hlo, "w") as f:
            f.write(hlo)
    dt = time_step(sess, m, feed, args.steps)
    toks = args.batch * args.seq / dt
    fpt = 3.0 * bert.bert_flops_per_token(cfg, args.seq)
    out = {
        "device": str(dev), "batch": args.batch, "seq": args.seq,
        "sec_per_step": round(dt, 5),
        "tokens_per_sec": round(toks, 1),
        "mfu": round(toks * fpt / peak, 4),
        "model_flops_T": round(fpt * args.batch * args.seq / 1e12, 3),
        "achieved_hbm_gbps": round(stats["bytes_gb"] / dt, 1),
        **stats,
    }
    print(json.dumps(out, indent=2))


if __name__ == "__main__":
    main()
