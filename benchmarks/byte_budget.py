#!/usr/bin/env python
"""Measure bytes-accessed / FLOPs of the headline train steps via XLA cost
analysis of the *lowered* (never executed) step — works on CPU, so the
77→55 GB ResNet byte claim and any f32-residual dtype regression are
machine-checkable without the TPU.

The numbers here calibrate tests/test_byte_budget.py's pinned budgets.

Usage: python benchmarks/byte_budget.py [--model resnet|bert|both]
       [--batch N] [--recompute]
"""

import argparse
import json
import os
import sys

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

os.environ.setdefault("JAX_PLATFORMS", "cpu")


def lowered_cost(train_op, loss, feed):
    """Plan the session step for (train_op, loss) under `feed`, lower and
    compile it WITHOUT running, and return XLA's cost analysis.

    Kernel-registry mode must be pinned to "off" (stf.kernels) by the
    caller AT GRAPH BUILD (the model builders run under
    ``stf.kernels.activate("off")``): the byte budgets were calibrated
    against the pre-registry lowerings, which "off" reproduces
    exactly. On this CPU gate "auto" would deliberately fall back to
    the composed XLA lowerings (materialized attention scores /
    log-softmax — the very traffic the budgets exist to catch),
    "force" routes EVERY kernel through interpret-mode Pallas whose
    per-grid-step HLO inflates XLA's byte accounting, and the fused
    optimizer tail's flat-slot slices are charged full-buffer reads by
    XLA's (pre-fusion) cost analysis. None of those is the calibrated
    baseline."""
    import simple_tensorflow_tpu as stf

    sess = stf.Session(config=stf.ConfigProto(kernel_registry="off"))
    sess.run(stf.global_variables_initializer())
    feeds = sess._normalize_feeds(feed)
    step = sess._plan([train_op, loss], feeds)
    assert step.has_device_stage, "train step lowered to host-only?"
    feed_args = {t.name: feeds[t] for t in step.feed_tensors}
    state = dict(sess._variable_store.values)
    compiled = step.jitted.lower(dict(state), feed_args,
                                 sess._base_key, np.uint32(0)).compile()
    cost = compiled.cost_analysis()
    if isinstance(cost, list):
        cost = cost[0]
    return {
        "flops": float(cost.get("flops", 0.0)),
        "bytes_accessed": float(cost.get("bytes accessed", 0.0)),
        "gbytes": round(float(cost.get("bytes accessed", 0.0)) / 1e9, 2),
        "tflops": round(float(cost.get("flops", 0.0)) / 1e12, 3),
    }


def resnet_cost(batch=256, image=224, recompute=False, s2d=False):
    import jax.numpy as jnp

    import simple_tensorflow_tpu as stf
    from simple_tensorflow_tpu.models import resnet

    from simple_tensorflow_tpu.kernels import registry as kreg

    stf.reset_default_graph()
    kwargs = {}
    if recompute:
        kwargs["recompute"] = True
    if s2d:
        kwargs["conv0_space_to_depth"] = True
    with kreg.activate("off"):  # calibrated pre-registry lowerings
        m = resnet.resnet50_train_model(batch_size=batch,
                                        image_size=image,
                                        dtype=stf.bfloat16,
                                        learning_rate=0.1, **kwargs)
    images, labels = resnet.synthetic_imagenet(batch, image)
    feed = {m["images"]: jnp.asarray(images, stf.bfloat16.np_dtype),
            m["labels"]: jnp.asarray(labels)}
    return lowered_cost(m["train_op"], m["loss"], feed)


def bert_cost(batch=24, seq_len=512, recompute=False):
    import jax.numpy as jnp

    import simple_tensorflow_tpu as stf
    from simple_tensorflow_tpu.kernels import registry as kreg
    from simple_tensorflow_tpu.models import bert

    stf.reset_default_graph()
    cfg = bert.BertConfig.base()
    max_pred = max(1, int(seq_len * 0.15))
    with kreg.activate("off"):  # calibrated pre-registry lowerings
        m = bert.bert_pretrain_model(
            batch_size=batch, seq_len=seq_len, max_predictions=max_pred,
            cfg=cfg, compute_dtype=stf.bfloat16, use_input_mask=True,
            recompute=recompute)
    batch_np = bert.synthetic_pretrain_batch(batch, seq_len, max_pred,
                                             vocab_size=cfg.vocab_size)
    batch_np["input_mask"] = np.ones((batch, seq_len), np.int32)
    feed = {m[k]: jnp.asarray(v) for k, v in batch_np.items()}
    return lowered_cost(m["train_op"], m["loss"], feed)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--model", default="both")
    ap.add_argument("--batch", type=int, default=None)
    ap.add_argument("--recompute", action="store_true")
    args = ap.parse_args()

    out = {}
    if args.model in ("resnet", "both"):
        out["resnet_b%d" % (args.batch or 256)] = resnet_cost(
            batch=args.batch or 256, recompute=args.recompute)
        if args.model == "both":  # progress line; final print has both
            print(json.dumps(out, indent=2), flush=True)
    if args.model in ("bert", "both"):
        out["bert_b%d_s512" % (args.batch or 24)] = bert_cost(
            batch=args.batch or 24, recompute=args.recompute)
    print(json.dumps(out, indent=2))


if __name__ == "__main__":
    main()
