"""Machine-checkable byte budgets for the headline train steps: the
77→~55 GB ResNet byte diagnosis and the BERT byte fixes must be guarded
by CI that runs WITHOUT the TPU.

Three layers of guard, each catching what the previous can't:

1. **VJP residual dtypes** — the round-3 ResNet regression was f32
   autodiff residuals (the saved ``(x - mean)`` of the two-pass BN
   variance), invisible in the stf graph and only expressible at the
   jax.vjp level. ``jax.vjp``'s returned closure carries the residuals as
   its pytree leaves, so we inspect them directly: a bf16 input must not
   produce an f32 residual of activation size.

2. **Compiled-step byte ratchet** — XLA cost analysis of the *compiled*
   bench-config train steps on CPU. Absolute numbers are CPU-fusion
   numbers (≈5x the TPU bytes — XLA-CPU barely fuses and upcasts bf16
   math internally), but the ratchet catches any structural regression
   that adds buffer traffic: calibrated 2026-07-30 at ResNet-b256
   367.2 GB / 6.374 TFLOP, BERT-b24-s512 167.6 GB / 8.839 TFLOP.

3. **FLOP pin** — catches accidental double compute (e.g. a broken
   forward-replay CSE) which a byte budget alone might miss.

The slow compiles (several minutes each, then cached by the persistent
jax compilation cache in .jax_cache/) can be skipped with
``STF_BYTE_BUDGET=0``.
"""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import simple_tensorflow_tpu as stf

_RUN_BUDGET = os.environ.get("STF_BYTE_BUDGET", "1") == "1"


# ---------------------------------------------------------------------------
# 1. VJP residual dtype guards
# ---------------------------------------------------------------------------

def _f32_residual_leaks(vjp_fn, activation_elems, allowed_elems=()):
    """f32 leaves of the vjp closure at activation size = saved residuals
    that will be written in forward and re-read in backward at 2x width."""
    leaks = []
    for leaf in jax.tree_util.tree_leaves(vjp_fn):
        if not hasattr(leaf, "dtype"):
            continue
        if leaf.dtype == jnp.float32 and leaf.size >= activation_elems \
                and leaf.size not in allowed_elems:
            leaks.append((leaf.shape, str(leaf.dtype)))
    return leaks


def test_bn_train_vjp_residuals_stay_bf16():
    """Training-mode fused BN on bf16 input: residuals must be the bf16 x
    plus per-channel f32 statistics — never a full-size f32 tensor (the
    round-3 bug: two-pass variance saved f32 ``x - mean``)."""
    from simple_tensorflow_tpu.ops import nn_impl

    n, h, w, c = 8, 16, 16, 32
    x = jnp.asarray(np.random.RandomState(0).randn(n, h, w, c),
                    jnp.bfloat16)
    scale = jnp.ones((c,), jnp.float32)
    offset = jnp.zeros((c,), jnp.float32)

    def f(x, scale, offset):
        return nn_impl._bn_train(x, scale, offset, 1e-3, (0, 1, 2))[0]

    _, vjp_fn = jax.vjp(f, x, scale, offset)
    leaks = _f32_residual_leaks(vjp_fn, activation_elems=x.size)
    assert not leaks, f"f32 activation-size BN residuals: {leaks}"


def test_matmul_vjp_residuals_stay_bf16():
    """bf16 matmul must not save f32 copies of its operands (the round-3
    ``preferred_element_type=f32`` bug doubled every dense layer's
    activation traffic)."""
    a = jnp.asarray(np.random.RandomState(1).randn(256, 512), jnp.bfloat16)
    b = jnp.asarray(np.random.RandomState(2).randn(512, 128), jnp.bfloat16)

    stf.reset_default_graph()
    ta = stf.placeholder(stf.bfloat16, [256, 512], name="a")
    tb = stf.placeholder(stf.bfloat16, [512, 128], name="b")
    out = stf.matmul(ta, tb)
    assert out.dtype.base_dtype == stf.bfloat16, (
        f"bf16 matmul emitted {out.dtype} (TF dtype semantics: output "
        "keeps the input dtype; the MXU accumulates f32 internally)")

    from simple_tensorflow_tpu.framework import lowering as lowering_mod

    pruned = lowering_mod.prune([out.op], fed_tensors={ta, tb})

    def f(av, bv):
        ctx = lowering_mod.LoweringContext({}, rng_root=None)
        ctx.env[ta] = av
        ctx.env[tb] = bv
        lowering_mod.execute_ops(ctx, pruned, fed={ta, tb})
        return ctx.env[out]

    _, vjp_fn = jax.vjp(f, a, b)
    leaks = _f32_residual_leaks(vjp_fn, activation_elems=min(a.size, b.size))
    assert not leaks, f"f32 matmul residuals: {leaks}"


def test_bert_layer_vjp_residuals_stay_bf16():
    """One transformer layer end-to-end at bf16: no f32 residual at
    activation size (embedding pipeline / LayerNorm / attention were the
    round-3 BERT byte sinks)."""
    from simple_tensorflow_tpu.framework import lowering as lowering_mod
    from simple_tensorflow_tpu.models import bert

    cfg = bert.BertConfig(vocab_size=128, hidden_size=64, num_layers=1,
                          num_heads=2, intermediate_size=128,
                          max_position=32, hidden_dropout=0.0,
                          attention_dropout=0.0)
    b_sz, s = 4, 32
    stf.reset_default_graph()
    ids = stf.placeholder(stf.int32, [b_sz, s], name="ids")
    seg = stf.placeholder(stf.int32, [b_sz, s], name="seg")
    out, _pooled, _emb = bert.bert_encoder(
        ids, seg, None, cfg, compute_dtype=stf.bfloat16, training=True)

    sess = stf.Session()
    sess.run(stf.global_variables_initializer())
    state = dict(sess._variable_store.values)
    pruned = lowering_mod.prune([out.op], fed_tensors={ids, seg})

    idv = jnp.asarray(np.random.RandomState(0).randint(
        0, cfg.vocab_size, (b_sz, s)), jnp.int32)
    sgv = jnp.zeros((b_sz, s), jnp.int32)

    def f(st):
        ctx = lowering_mod.LoweringContext(st,
                                           rng_root=jax.random.key(0))
        ctx.env[ids] = idv
        ctx.env[seg] = sgv
        lowering_mod.execute_ops(ctx, pruned, fed={ids, seg})
        return ctx.env[out]

    _, vjp_fn = jax.vjp(f, state)
    # param-sized f32 is fine (master weights); activation-size is not
    activation_elems = b_sz * s * cfg.hidden_size
    param_sizes = {int(np.prod(v.shape)) for v in state.values()}
    leaks = _f32_residual_leaks(vjp_fn, activation_elems,
                                allowed_elems=param_sizes)
    assert not leaks, f"f32 BERT residuals: {leaks[:8]}"


# ---------------------------------------------------------------------------
# 2+3. Compiled-step byte ratchet + FLOP pin (slow; cached after 1st run)
# ---------------------------------------------------------------------------

# calibrated on CPU 2026-07-30 (see module docstring); ~9% headroom
_RESNET_BYTES_BUDGET = 400e9
_RESNET_FLOPS_RANGE = (5.7e12, 7.1e12)   # 6.374 measured
_BERT_BYTES_BUDGET = 185e9
_BERT_FLOPS_RANGE = (8.0e12, 9.8e12)     # 8.839 measured


def _enable_cache():
    from simple_tensorflow_tpu.compiler import aot

    aot.enable_persistent_cache()


# ---------------------------------------------------------------------------
# 4. Function-aware optimizer ratchet (PR 1 tentpole): post-optimization
#    cost-model bytes/FLOPs of a cond+scan model are pinned so in-body
#    CSE/layout wins can't silently regress. Fast (static cost model
#    only, no compile) — always runs.
# ---------------------------------------------------------------------------

# calibrated 2026-08-03: unopt 1.154e7 F / 8.06e6 B -> opt 1.141e7 F /
# 6.88e6 B (NCHW per-op transposes cancelled in the cond branch and the
# scan body, Exp CSE'd in-body); ~8% headroom on the pins
_COND_SCAN_BYTES_BUDGET = 7.4e6
_COND_SCAN_FLOPS_BUDGET = 1.23e7


def _build_cond_scan_model():
    import simple_tensorflow_tpu as stf_mod

    stf_mod.reset_default_graph()
    rng = np.random.RandomState(0)
    n, c, hw, steps = 4, 8, 16, 8
    x = stf_mod.placeholder(stf_mod.float32, [n, c, hw, hw], name="bx")
    w1 = stf_mod.constant(rng.randn(3, 3, c, c).astype(np.float32) * 0.2,
                          name="bw1")
    w2 = stf_mod.constant(rng.randn(3, 3, c, c).astype(np.float32) * 0.2,
                          name="bw2")
    scale = stf_mod.constant(np.ones(c, np.float32))
    offset = stf_mod.constant(np.zeros(c, np.float32))

    def branch_t():
        h = stf_mod.nn.conv2d(x, w1, strides=[1, 1, 1, 1],
                              padding="SAME", data_format="NCHW")
        h, _, _ = stf_mod.nn.fused_batch_norm(h, scale, offset,
                                              data_format="NCHW")
        return stf_mod.nn.relu(h)

    def branch_f():
        h = stf_mod.nn.conv2d(x, w2, strides=[1, 1, 1, 1],
                              padding="SAME", data_format="NCHW")
        return stf_mod.nn.relu(h)

    h0 = stf_mod.cond(stf_mod.reduce_sum(x) > 0.0, branch_t, branch_f)
    dummy = stf_mod.constant(np.zeros((steps, 1), np.float32))

    def body(carry, _):
        h = stf_mod.nn.conv2d(carry, w1, strides=[1, 1, 1, 1],
                              padding="SAME", data_format="NCHW")
        h, _, _ = stf_mod.nn.fused_batch_norm(h, scale, offset,
                                              data_format="NCHW")
        a = stf_mod.exp(carry)
        b = stf_mod.exp(carry)  # in-body CSE target
        return stf_mod.nn.relu(h) + 0.0 * (a + b)

    out = stf_mod.scan(body, dummy, initializer=h0)
    res = stf_mod.reduce_mean(out[-1], name="budget_res")
    return x, res


def test_cond_scan_post_optimization_cost_ratchet():
    import json

    from simple_tensorflow_tpu.framework import (cost_model, graph_io,
                                                 optimizer)

    x, res = _build_cond_scan_model()
    est_unopt = cost_model.estimate(res, feeds=[x])
    gd = graph_io.graph_to_graphdef(stf.get_default_graph())
    opt = optimizer.optimize(gd, keep=[res.name, x.name])

    stf.reset_default_graph()
    graph_io.import_graph_def(json.dumps(opt), name="")
    g = stf.get_default_graph()
    x2 = g.as_graph_element("bx:0", True, False)
    r2 = g.as_graph_element(res.name, True, False)
    est_opt = cost_model.estimate(r2, feeds=[x2])

    # the optimizer must WIN: in-body layout + CSE cut modeled traffic
    assert est_opt.bytes_accessed < est_unopt.bytes_accessed, (
        f"optimization increased modeled bytes: "
        f"{est_opt.bytes_accessed:.3g} >= {est_unopt.bytes_accessed:.3g}")
    # and the post-optimization numbers are pinned (ratchet)
    assert est_opt.bytes_accessed <= _COND_SCAN_BYTES_BUDGET, (
        f"cond/scan post-opt bytes regressed: {est_opt.bytes_accessed:.4g}"
        f" > {_COND_SCAN_BYTES_BUDGET:.4g} (calibrated 6.88e6; in-body "
        "layout/CSE may have stopped firing)")
    assert est_opt.flops <= _COND_SCAN_FLOPS_BUDGET, (
        f"cond/scan post-opt FLOPs regressed: {est_opt.flops:.4g} > "
        f"{_COND_SCAN_FLOPS_BUDGET:.4g} (calibrated 1.141e7)")
    # the numbers stay real: the rewritten graph computes the same value
    xv = np.random.RandomState(1).randn(4, 8, 16, 16).astype(np.float32)
    with stf.Session() as s2:
        got = np.asarray(s2.run(r2, {x2: xv}))
    x, res = _build_cond_scan_model()
    with stf.Session() as s1:
        expected = np.asarray(s1.run(res, {x: xv}))
    np.testing.assert_allclose(got, expected, rtol=1e-4, atol=1e-5)


def _lowered_cost(train_op, loss, feed):
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


def _resnet_cost(batch=256, image=224):
    from simple_tensorflow_tpu.kernels import registry as kreg
    from simple_tensorflow_tpu.models import resnet

    stf.reset_default_graph()
    with kreg.activate("off"):  # calibrated pre-registry lowerings
        m = resnet.resnet50_train_model(batch_size=batch,
                                        image_size=image,
                                        dtype=stf.bfloat16,
                                        learning_rate=0.1)
    images, labels = resnet.synthetic_imagenet(batch, image)
    feed = {m["images"]: jnp.asarray(images, stf.bfloat16.np_dtype),
            m["labels"]: jnp.asarray(labels)}
    return _lowered_cost(m["train_op"], m["loss"], feed)


def _bert_cost(batch=24, seq_len=512):
    from simple_tensorflow_tpu.kernels import registry as kreg
    from simple_tensorflow_tpu.models import bert

    stf.reset_default_graph()
    cfg = bert.BertConfig.base()
    max_pred = max(1, int(seq_len * 0.15))
    with kreg.activate("off"):  # calibrated pre-registry lowerings
        m = bert.bert_pretrain_model(
            batch_size=batch, seq_len=seq_len, max_predictions=max_pred,
            cfg=cfg, compute_dtype=stf.bfloat16, use_input_mask=True)
    batch_np = bert.synthetic_pretrain_batch(batch, seq_len, max_pred,
                                             vocab_size=cfg.vocab_size)
    batch_np["input_mask"] = np.ones((batch, seq_len), np.int32)
    feed = {m[k]: jnp.asarray(v) for k, v in batch_np.items()}
    return _lowered_cost(m["train_op"], m["loss"], feed)


@pytest.mark.skipif(not _RUN_BUDGET, reason="STF_BYTE_BUDGET=0")
def test_resnet_train_step_byte_budget():
    _enable_cache()
    cost = _resnet_cost(batch=256)
    assert cost["bytes_accessed"] <= _RESNET_BYTES_BUDGET, (
        f"ResNet-b256 step bytes regressed: {cost['gbytes']} GB > "
        f"{_RESNET_BYTES_BUDGET / 1e9} GB budget (calibrated 367 GB; a "
        "jump of this size usually means f32 activations crept back in)")
    lo, hi = _RESNET_FLOPS_RANGE
    assert lo <= cost["flops"] <= hi, (
        f"ResNet-b256 step FLOPs {cost['tflops']} TF outside "
        f"[{lo / 1e12}, {hi / 1e12}] — double compute or dropped work?")


@pytest.mark.skipif(not _RUN_BUDGET, reason="STF_BYTE_BUDGET=0")
def test_bert_train_step_byte_budget():
    _enable_cache()
    cost = _bert_cost(batch=24)
    assert cost["bytes_accessed"] <= _BERT_BYTES_BUDGET, (
        f"BERT-b24-s512 step bytes regressed: {cost['gbytes']} GB > "
        f"{_BERT_BYTES_BUDGET / 1e9} GB budget (calibrated 167.6 GB)")
    lo, hi = _BERT_FLOPS_RANGE
    assert lo <= cost["flops"] <= hi, (
        f"BERT step FLOPs {cost['tflops']} TF outside "
        f"[{lo / 1e12}, {hi / 1e12}]")
