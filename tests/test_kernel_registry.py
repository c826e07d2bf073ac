"""stf.kernels — the Pallas/XLA kernel routing tier (ISSUE 11).

Covers the registry contract end to end on the CPU test mesh (Pallas in
interpret mode):

- registry fuzz: random (shape, dtype, mode) draws assert the routed
  and fallback lowerings agree — bit-identical where the two
  implementations share elementwise-only math (fused optimizer
  updates, fused dropout+bias+residual), tight float tolerances where
  reduction order legitimately differs (attention/layer-norm/xent) —
  and that every non-routed decision is explained by exactly one
  ``/stf/kernels/fallback{op, reason}`` cell;
- ``off`` mode restores the pre-registry lowerings exactly: fused
  graph ops keep Pallas, optimizers rebuild the per-variable assign
  tail, trajectories match bit-for-bit;
- the routing rule: a decision is a pure function of (op, shapes and
  dtypes, backend, mesh, mode) that runs no lowering and writes no
  file, and the two sets of decisions the chip has printed stay as
  they are;
- the zoo force gate: transformer + long_context route their attention
  ops under ``force``;
- seeded dropout reproducibility across implementation swaps.
"""

import json
import os
import subprocess
import sys

import jax
import numpy as np
import pytest

import simple_tensorflow_tpu as stf
from simple_tensorflow_tpu.kernels import registry as kreg
from simple_tensorflow_tpu.ops.pallas import _np_of, flat_group_key


@pytest.fixture(autouse=True)
def _clean_registry_state():
    stf.reset_default_graph()
    kreg.set_mode(None)
    kreg.clear_decisions()
    yield
    kreg.set_mode(None)
    kreg.clear_decisions()
    stf.reset_default_graph()


def _counter_totals():
    routed = sum(c.value() for c in kreg.metric_routed.cells().values())
    fallback = {labels: cell.value()
                for labels, cell in kreg.metric_fallback.cells().items()}
    return routed, fallback


_KNOWN_REASONS = {"mode_off", "forced", "ineligible_dtype",
                  "ineligible_shape", "ineligible_bias",
                  "mesh_auto_partitioned", "interpret_backend",
                  "cost_model", "cost_model_uncertain", "unpriced",
                  "no_graph_key", "unknown_shape"}


# ---------------------------------------------------------------------------
# modes
# ---------------------------------------------------------------------------

class TestModes:
    def test_off_mode_picks_legacy_impl(self):
        # fused graph ops lowered through Pallas before the registry
        # existed; composed ops through jnp — off reproduces both
        key = kreg.aval_key(
            np.zeros((1, 2, 8, 4), np.float32),
            np.zeros((1, 2, 8, 4), np.float32),
            np.zeros((1, 2, 8, 4), np.float32), None,
            causal=False, dropout=False)
        assert kreg.decide("FlashAttention", key, mode="off") == (
            "pallas", "mode_off")
        xkey = kreg.aval_key(np.zeros((4, 16), np.float32),
                             np.zeros((4,), np.int32))
        assert kreg.decide("SparseSoftmaxCrossEntropyWithLogits", xkey,
                           mode="off") == ("xla", "mode_off")

    def test_force_routes_eligible_and_respects_ineligibility(self):
        key = kreg.aval_key(
            np.zeros((1, 2, 8, 4), np.float32),
            np.zeros((1, 2, 8, 4), np.float32),
            np.zeros((1, 2, 8, 4), np.float32), None,
            causal=False, dropout=False)
        assert kreg.decide("FlashAttention", key, mode="force") == (
            "pallas", "forced")
        # per-head bias: the kernel cannot express it, force falls back
        bad = kreg.aval_key(
            np.zeros((1, 2, 8, 4), np.float32),
            np.zeros((1, 2, 8, 4), np.float32),
            np.zeros((1, 2, 8, 4), np.float32),
            np.zeros((1, 2, 8, 8), np.float32),
            causal=False, dropout=False)
        impl, reason = kreg.decide("FlashAttention", bad, mode="force")
        assert impl == "xla" and reason == "ineligible_bias"

    def test_auto_on_cpu_falls_back_interpret(self):
        key = kreg.aval_key(np.zeros((8, 32), np.float32),
                            np.zeros((32,), np.float32),
                            np.zeros((32,), np.float32))
        impl, reason = kreg.decide("FusedLayerNorm", key, mode="auto")
        assert impl == "xla" and reason == "interpret_backend"

    def test_session_config_scopes_mode(self):
        a = [np.random.RandomState(i).randn(1, 2, 16, 8).astype(np.float32)
             for i in range(3)]
        t = stf.nn.fused_attention(*[stf.constant(x) for x in a])
        routed0, _ = _counter_totals()
        with stf.Session(config=stf.ConfigProto(
                kernel_registry="force")) as sess:
            sess.run(t)
        routed1, _ = _counter_totals()
        assert routed1 > routed0  # traced under force -> Pallas


# ---------------------------------------------------------------------------
# registry fuzz (ISSUE 11 satellite)
# ---------------------------------------------------------------------------

# concrete inputs for a decision key: what the parity fuzz feeds both
# lowerings of a kernel

def _rand(shape, dt, seed=0):
    rng = np.random.RandomState(seed)
    d = _np_of(dt)
    if d.kind in "iu":
        return rng.randint(0, 4, size=shape).astype(d)
    return rng.randn(*shape).astype(np.float32).astype(d)


def _flash_case(key):
    (qs, qd), (ks, kd), (vs, vd), bias = key[:4]
    statics = dict(key[4:])
    args = [_rand(qs, qd, 0), _rand(ks, kd, 1), _rand(vs, vd, 2)]
    kw = {"causal": bool(statics.get("causal", False))}
    if bias is not None:
        kw["bias"] = _rand(bias[0], bias[1], 3)
    if statics.get("dropout"):
        kw["dropout_rate"] = 0.1
        kw["dropout_seed"] = np.asarray([7], np.int32)
    return tuple(args), kw


def _ln_case(key):
    (xs, xd), (gs, gd), (bs, bd) = key[:3]
    return ((_rand(xs, xd, 0), _rand(gs, gd, 1), _rand(bs, bd, 2)), {})


def _xent_case(key):
    (ls, ld), (labs, labd) = key[:2]
    statics = dict(key[2:])
    logits = _rand(ls, ld, 0)
    labels = np.random.RandomState(1).randint(
        0, ls[-1], size=labs).astype(_np_of(labd))
    return ((logits, labels),
            {"label_smoothing": 0.1 if statics.get("label_smoothing")
             else 0.0})


def _sparse_xent_case(key):
    (logits, labels), _ = _xent_case(key)
    return ((logits, labels), {})


def _qmm_case(key):
    (xs, xd), (ws, wd), (ss, sd) = key[:3]
    rng = np.random.RandomState(0)
    x = rng.randn(*xs).astype(_np_of(xd))
    wq = rng.randint(-127, 128, size=ws).astype(np.int8)
    scale = (rng.rand(*ss).astype(np.float32) * 0.1 + 0.01)
    return ((x, wq, scale), {})


def _dbr_case(key):
    (xs, xd), (rs, rd), bias = key[:3]
    statics = dict(key[3:])
    args = [_rand(xs, xd, 0), _rand(rs, rd, 1)]
    kw = {"rate": float(statics.get("rate", 0.1)),
          "seed": np.asarray([5], np.int32)}
    if bias is not None:
        kw["bias"] = _rand(bias[0], bias[1], 2)
    return tuple(args), kw


def _adam_case(key):
    st = dict(key)
    n = int(st["n"])
    pdt, udt = st["pdt"], st["udt"]
    rng = np.random.RandomState(0)
    p = rng.randn(n).astype(_np_of(pdt))
    m = rng.randn(n).astype(_np_of(udt)) * 0.01
    v = np.abs(rng.randn(n)).astype(_np_of(udt)) * 0.01
    g = rng.randn(n).astype(_np_of(udt))
    alpha = np.asarray(0.001, _np_of(udt))
    return ((p, m, v, g, alpha),
            {"beta1": 0.9, "beta2": 0.999, "eps": 1e-8})


def _momentum_case(key):
    st = dict(key)
    n = int(st["n"])
    pdt, udt = st["pdt"], st["udt"]
    rng = np.random.RandomState(0)
    p = rng.randn(n).astype(_np_of(pdt))
    acc = rng.randn(n).astype(_np_of(udt)) * 0.01
    g = rng.randn(n).astype(_np_of(udt))
    lr = np.asarray(0.01, _np_of(udt))
    mu = np.asarray(0.9, _np_of(udt))
    return ((p, acc, g, lr, mu), {"use_nesterov": False})


def _decode_attn_case(key):
    (qs, qd), (ks, kd), (vs, vd), bias = key[:4]
    args = [_rand(qs, qd, 0), _rand(ks, kd, 1), _rand(vs, vd, 2),
            np.full((qs[0],), ks[1] // 2 + 1, np.int32)]
    kw = {}
    if bias is not None:
        kw["bias"] = _rand(bias[0], bias[1], 3)
    return tuple(args), kw


def _paged_attn_case(key):
    """Pools, a page table whose rows share their first page and end in
    the scratch page (the pool's last), and lengths inside the table."""
    (qs, qd), (ps, pd), (ts, _td) = key[:3]
    b, nb = ts
    rng = np.random.RandomState(4)
    tables = np.full(ts, ps[0] - 1, np.int32)
    lengths = rng.randint(1, nb * ps[1] - (qs[1] if len(qs) == 4 else 0)
                          + 1, size=b).astype(np.int32)
    for r in range(b):
        live = -(-(int(lengths[r]) + (qs[1] if len(qs) == 4 else 0))
                 // ps[1])
        tables[r, :live] = rng.choice(ps[0] - 1, live, replace=False)
        tables[r, 0] = 0
    return ((_rand(qs, qd, 0), _rand(ps, pd, 1), _rand(ps, pd, 2), tables,
             lengths), {"causal_offset": len(qs) == 4})


_MAKE_CASE = {
    "PagedDecodeAttention": _paged_attn_case,
    "FlashAttention": _flash_case,
    "FusedLayerNorm": _ln_case,
    "FusedSoftmaxXent": _xent_case,
    "SparseSoftmaxCrossEntropyWithLogits": _sparse_xent_case,
    "QuantMatMul": _qmm_case,
    "FusedDropoutBiasResidual": _dbr_case,
    "FusedAdamUpdate": _adam_case,
    "FusedMomentumUpdate": _momentum_case,
    "DecodeAttention": _decode_attn_case,
}


def _draw_case(rng):
    """One random (kernel, key) draw; returns (op_type, key, exact)
    where exact marks elementwise-only kernels (bit-identical impls)."""
    kind = rng.choice(["flash", "ln", "xent", "qmm", "dbr", "adam",
                       "momentum"])
    f_dt = rng.choice(["float32", "bfloat16"])
    if kind == "flash":
        b, h = int(rng.randint(1, 3)), int(rng.randint(1, 3))
        s = int(rng.randint(3, 40))
        d = int(rng.choice([4, 8, 12]))
        causal = bool(rng.randint(2))
        shape = (b, h, s, d)
        key = kreg.aval_key(
            np.zeros(shape, np.float32).astype(f_dt == "bfloat16" and
                                               np.float32 or np.float32),
            np.zeros(shape, np.float32), np.zeros(shape, np.float32),
            None, causal=causal, dropout=False)
        return "FlashAttention", key, False
    if kind == "ln":
        rows, n = int(rng.randint(1, 24)), int(rng.randint(3, 96))
        key = kreg.aval_key(np.zeros((rows, n), np.float32),
                            np.zeros((n,), np.float32),
                            np.zeros((n,), np.float32))
        return "FusedLayerNorm", key, False
    if kind == "xent":
        rows, v = int(rng.randint(1, 12)), int(rng.randint(4, 260))
        key = kreg.aval_key(np.zeros((rows, v), np.float32),
                            np.zeros((rows,), np.int32),
                            label_smoothing=bool(rng.randint(2)))
        return "FusedSoftmaxXent", key, False
    if kind == "qmm":
        m, k, n = (int(rng.randint(1, 48)) for _ in range(3))
        key = kreg.aval_key(np.zeros((m, k), np.float32),
                            np.zeros((k, n), np.int8),
                            np.zeros((n,), np.float32))
        return "QuantMatMul", key, False
    if kind == "dbr":
        rows, n = int(rng.randint(1, 24)), int(rng.randint(2, 48))
        has_bias = bool(rng.randint(2))
        key = kreg.aval_key(
            np.zeros((rows, n), np.float32),
            np.zeros((rows, n), np.float32),
            np.zeros((n,), np.float32) if has_bias else None,
            rate=float(rng.choice([0.1, 0.37])))
        return "FusedDropoutBiasResidual", key, True
    n = int(rng.randint(1, 4000))
    key = flat_group_key(n, "float32", "float32")
    return ("FusedAdamUpdate" if kind == "adam"
            else "FusedMomentumUpdate"), key, True


def _assert_lowerings_agree(op_type, key, exact):
    kd = kreg._KERNELS[op_type]
    args, kwargs = _MAKE_CASE[op_type](key)
    out_p = jax.block_until_ready(kd.impls["pallas"](*args, **kwargs))
    out_x = jax.block_until_ready(kd.impls["xla"](*args, **kwargs))
    flat_p = jax.tree_util.tree_leaves(out_p)
    flat_x = jax.tree_util.tree_leaves(out_x)
    assert len(flat_p) == len(flat_x)
    for a, b in zip(flat_p, flat_x):
        a = np.asarray(a)
        b = np.asarray(b)
        if np.issubdtype(a.dtype, np.integer):
            # int outputs: bit-identical, no excuses
            np.testing.assert_array_equal(a, b, err_msg=op_type)
            continue
        a = a.astype(np.float32)
        b = b.astype(np.float32)
        if exact:
            # elementwise-only kernels: identical op sequence; the
            # only permitted divergence is FMA contraction (XLA
            # fuses multiply-adds differently across the two
            # compilations), which compounds to a few ulps through
            # the m/v/param chain — measured ≤7; budget 8. True
            # bit-exactness across modes is pinned end-to-end by
            # test_fused_optimizer_bitexact_and_killable.
            ai = a.view(np.int32).astype(np.int64)
            bi = b.view(np.int32).astype(np.int64)
            am = np.where(ai < 0, np.int64(-2**31) - ai, ai)
            bm = np.where(bi < 0, np.int64(-2**31) - bi, bi)
            assert np.abs(am - bm).max() <= 8, op_type
        else:
            # reduction-bearing kernels (online softmax, row stats,
            # int8 accumulation): summation order differs
            np.testing.assert_allclose(a, b, atol=5e-5, rtol=5e-5,
                                       err_msg=op_type)


@pytest.mark.parametrize("op_type,key", [
    ("SparseSoftmaxCrossEntropyWithLogits",
     kreg.aval_key(np.zeros((7, 133), np.float32),
                   np.zeros((7,), np.int32))),
    ("DecodeAttention",
     kreg.aval_key(np.zeros((3, 2, 8), np.float32),
                   np.zeros((3, 24, 2, 8), np.float32),
                   np.zeros((3, 24, 2, 8), np.float32), None,
                   has_bias=False)),
    ("PagedDecodeAttention",
     kreg.aval_key(np.zeros((3, 4, 8), np.float32),
                   np.zeros((14, 8, 32), np.float32),
                   np.zeros((3, 4), np.int32))),
    ("PagedDecodeAttention",
     kreg.aval_key(np.zeros((3, 8, 4, 8), np.float32),
                   np.zeros((14, 8, 32), np.float32),
                   np.zeros((3, 4), np.int32))),
], ids=["sparse_xent", "decode_attention", "paged_decode_attention",
        "paged_block_attention"])
def test_lowerings_the_fuzz_does_not_draw_agree(op_type, key):
    assert kreg._KERNELS[op_type].eligible(key) is None
    _assert_lowerings_agree(op_type, key, exact=False)


def test_registry_fuzz_parity_and_counters():
    """Random (shape, dtype, mode) draws: the two lowerings agree on
    every eligible key, and the routed/fallback counters explain every
    decision (one increment each, reason from the documented set)."""
    rng = np.random.RandomState(1234)
    for draw in range(18):
        op_type, key, exact = _draw_case(rng)
        mode = str(rng.choice(["off", "auto", "force"]))
        kd = kreg._KERNELS[op_type]
        if kd.eligible(key):
            continue  # ineligible draws covered by the mode tests
        _assert_lowerings_agree(op_type, key, exact)
        routed0, fb0 = _counter_totals()
        impl, reason = kreg.decide(op_type, key, mode=mode)
        routed1, fb1 = _counter_totals()
        assert reason in _KNOWN_REASONS, (op_type, reason)
        if impl == "pallas":
            assert routed1 == routed0 + 1
            assert fb1 == fb0
        else:
            assert routed1 == routed0
            diff = {k: fb1.get(k, 0) - fb0.get(k, 0) for k in fb1}
            bumped = {k: v for k, v in diff.items() if v}
            assert bumped == {(op_type, reason): 1}


# ---------------------------------------------------------------------------
# fused optimizer tail: bit-exact vs the per-variable chains
# ---------------------------------------------------------------------------

def _train_weights(mode, optimizer_fn, steps=3):
    kreg.set_mode(mode)
    kreg.clear_decisions()
    stf.reset_default_graph()
    x = stf.placeholder(stf.float32, [4, 8], "x")
    w = stf.get_variable(
        "w", [8, 5], initializer=stf.random_normal_initializer(seed=1))
    wb = stf.get_variable("wb", [8, 5], dtype=stf.bfloat16,
                          initializer=stf.zeros_initializer())
    y = (stf.matmul(x, w) +
         stf.cast(stf.matmul(stf.cast(x, stf.bfloat16), wb), stf.float32))
    loss = stf.reduce_mean(stf.square(y))
    opt = optimizer_fn()
    gs = stf.train.get_or_create_global_step()
    train = opt.minimize(loss, global_step=gs)
    with stf.Session() as sess:
        sess.run(stf.global_variables_initializer())
        xv = np.random.RandomState(0).randn(4, 8).astype(np.float32)
        losses = [np.asarray(sess.run([loss, train], {x: xv})[0])
                  for _ in range(steps)]
        ops = {o.type for o in stf.get_default_graph().get_operations()}
        slots = {f"{sn}/{v.name}": np.asarray(sess.run(opt.get_slot(v, sn)))
                 for sn in opt.get_slot_names() for v in (w, wb)
                 if opt.get_slot(v, sn) is not None}
        return (np.asarray(losses), np.asarray(sess.run(w)),
                np.asarray(sess.run(wb)).astype(np.float32), slots, ops,
                int(np.asarray(sess.run(gs))))


@pytest.mark.parametrize("opt_fn,fused_type", [
    (lambda: stf.train.AdamOptimizer(0.01), "FusedAdamUpdate"),
    (lambda: stf.train.MomentumOptimizer(0.05, 0.9), "FusedMomentumUpdate"),
    (lambda: stf.train.MomentumOptimizer(0.05, 0.9, use_nesterov=True),
     "FusedMomentumUpdate"),
])
def test_fused_optimizer_bitexact_and_killable(opt_fn, fused_type):
    la, wa, wba, sa, opsa, gsa = _train_weights("auto", opt_fn)
    lf, wf, wbf, sf, opsf, gsf = _train_weights("force", opt_fn)
    lo, wo, wbo, so, opso, gso = _train_weights("off", opt_fn)
    # graph shape: fused op present under auto/force, ABSENT under off
    # (set_mode("off") restores the per-variable assign tail exactly)
    assert fused_type in opsa and fused_type in opsf
    assert fused_type not in opso
    assert "AssignSub" in opso and "AssignSub" not in opsa
    # trajectories bit-exact across all three modes (params, bf16
    # params, every slot), global step advances identically
    for got in ((la, wa, wba, sa, gsa), (lf, wf, wbf, sf, gsf)):
        np.testing.assert_array_equal(got[0], lo)
        np.testing.assert_array_equal(got[1], wo)
        np.testing.assert_array_equal(got[2], wbo)
        assert got[4] == gso
        for k, v in so.items():
            np.testing.assert_array_equal(got[3][k], v, err_msg=k)


def test_fused_adam_with_tensor_lr_schedule():
    def make():
        gs = stf.train.get_or_create_global_step()
        lr = stf.train.exponential_decay(0.01, gs, 2, 0.5)
        return stf.train.AdamOptimizer(lr)

    la, wa, _, _, opsa, _ = _train_weights("auto", make)
    lo, wo, _, _, opso, _ = _train_weights("off", make)
    assert "FusedAdamUpdate" in opsa and "FusedAdamUpdate" not in opso
    np.testing.assert_array_equal(la, lo)
    np.testing.assert_array_equal(wa, wo)


def test_fused_update_read_after_write_visible():
    # a read with a control dep on the fused op observes the NEW value
    # (read-your-write contract, state_ops.ReadVariable semantics)
    stf.reset_default_graph()
    x = stf.placeholder(stf.float32, [2, 3], "x")
    w = stf.get_variable("w", [3, 2],
                         initializer=stf.ones_initializer())
    loss = stf.reduce_sum(stf.matmul(x, w))
    opt = stf.train.AdamOptimizer(0.1)
    train = opt.minimize(loss)
    g = stf.get_default_graph()
    with g.control_dependencies([train]):
        w_after = w.read_value()
    with stf.Session() as sess:
        sess.run(stf.global_variables_initializer())
        before = np.asarray(sess.run(w))
        after = np.asarray(sess.run(
            w_after, {x: np.ones((2, 3), np.float32)}))
    assert not np.array_equal(before, after)


# ---------------------------------------------------------------------------
# the routing rule: pure, and what the chip has printed stays
# ---------------------------------------------------------------------------

def _aval(shape, dtype="bfloat16"):
    return jax.ShapeDtypeStruct(shape, dtype)


def _flash_key(b, h, s, d, dtype="bfloat16", bias=False, **statics):
    qkv = _aval((b, h, s, d), dtype)
    return kreg.aval_key(qkv, qkv, qkv,
                         _aval((b, 1, 1, s), "float32") if bias else None,
                         **{"causal": False, "dropout": False, **statics})


def _ln_key(rows, n, dtype="bfloat16"):
    return kreg.aval_key(_aval((rows, n), dtype), _aval((n,), "float32"),
                         _aval((n,), "float32"))


def _decode_key(b, length, h, d, dtype="bfloat16"):
    cache = _aval((b, length, h, d), dtype)
    return kreg.aval_key(_aval((b, h, d), dtype), cache, cache, None,
                         has_bias=False)


def _paged_key(b, kq, h, d, page_len, n_blocks, pages,
               dtype="bfloat16"):
    q = (b, h, d) if kq == 1 else (b, kq, h, d)
    return kreg.aval_key(_aval(q, dtype),
                         _aval((pages, page_len, h * d), dtype),
                         _aval((b, n_blocks), "int32"))


# one key per registered kernel type, at widths a chip would be given
_RULE_KEYS = {
    "FlashAttention": _flash_key(8, 16, 1024, 64, causal=True),
    "FlashAttentionDropout": _flash_key(8, 16, 1024, 64, dropout=True),
    "RingAttention": _flash_key(2, 16, 4096, 64, causal=True),
    "FusedLayerNorm": _ln_key(8192, 1024),
    "FusedSoftmaxXent": kreg.aval_key(
        _aval((4096, 32000), "float32"), _aval((4096,), "int32"),
        label_smoothing=True),
    "SparseSoftmaxCrossEntropyWithLogits": kreg.aval_key(
        _aval((4096, 32000), "float32"), _aval((4096,), "int32")),
    "QuantMatMul": kreg.aval_key(
        _aval((64, 256)), _aval((256, 128), "int8"),
        _aval((128,), "float32")),
    "FusedDropoutBiasResidual": kreg.aval_key(
        _aval((8192, 1024)), _aval((8192, 1024)), _aval((1024,)),
        rate=0.1),
    "FusedAdamUpdate": flat_group_key(300_000, "float32", "float32"),
    "FusedMomentumUpdate": flat_group_key(25_000_000, "bfloat16",
                                          "float32"),
    "DecodeAttention": _decode_key(16, 4096, 32, 128),
    "PagedDecodeAttention": _paged_key(16, 1, 32, 128, 128, 32, 1025),
    # 32 rows x 64 absorbed heads over a pool of 640-wide latent rows
    "PagedLatentAttention": kreg.aval_key(
        _aval((32, 64, 640)), _aval((1201, 512, 640)),
        _aval((32, 37), "int32"), value_dim=512),
    # 256 rows x 64 heads x 64 over a float32 slot pool of 2.1 MB states
    "SSMStateUpdate": kreg.aval_key(
        _aval((256, 64, 64)), _aval((257, 32, 128, 128), "float32"),
        _aval((256, 8, 128))),
    # 128 rows x 64 heads of a 128 x 128 float32 matrix state (4.2 MB)
    "KDAStateUpdate": kreg.aval_key(
        _aval((128, 64, 128), "float32"),
        _aval((129, 64, 128, 128), "float32"),
        _aval((128, 64, 128), "float32")),
    # 256 rows of [B | C | x~] 3 x 2048 over a pool of 2 x 2048 windows
    "ShortConvUpdate": kreg.aval_key(
        _aval((256, 6144)), _aval((257, 32, 128)), _aval((3, 2048))),
}


def _must_not_run(*args, **kwargs):
    raise AssertionError("a routing decision ran a lowering")


@pytest.fixture
def on_tpu(monkeypatch):
    """The registry as a v5e would see it, on the CPU: the backend it
    asks for and the published peaks its roofline gate prices with."""
    from simple_tensorflow_tpu.utils import perf

    monkeypatch.setattr(kreg, "backend", lambda: "tpu")
    monkeypatch.setattr(perf, "chip_spec",
                        lambda device=None: perf.CHIP_TABLE["TPU v5 lite"][:2])


class TestRule:
    def test_one_key_for_every_registered_kernel(self):
        assert set(_RULE_KEYS) == set(kreg.kernel_types())

    @pytest.mark.parametrize("op_type", sorted(_RULE_KEYS))
    def test_deciding_runs_nothing_and_repeats(self, on_tpu, monkeypatch,
                                               op_type):
        kd = kreg._KERNELS[op_type]
        monkeypatch.setattr(kd, "impls", {"pallas": _must_not_run,
                                          "xla": _must_not_run})
        key = _RULE_KEYS[op_type]
        assert kd.eligible(key) is None
        first = kreg.decide(op_type, key, mode="auto", count=False)
        assert first[0] in ("pallas", "xla")
        assert first[1] in ("cost_model", "cost_model_uncertain")
        kreg.clear_decisions()
        assert kreg.decide(op_type, key, mode="auto", count=False) == first
        # the offline report is the same rule
        assert kreg._route(kd, key, "auto", "tpu") == first

    @pytest.mark.parametrize("gate,reason", [
        (lambda key, bk: (None, "cost_model_uncertain"),
         "cost_model_uncertain"),
        (None, "unpriced"),         # a kernel registered without a gate
    ], ids=["uncertain", "unpriced"])
    def test_abstaining_gate_takes_the_kernel_on_a_tpu_only(
            self, monkeypatch, gate, reason):
        kd = kreg.register_kernel(
            "TestKernelAbstains",
            impls={"pallas": _must_not_run, "xla": _must_not_run},
            legacy="xla", cost_gate=gate)
        key = kreg.aval_key(np.zeros((4,), np.float32))
        try:
            assert kreg.decide(kd.op_type, key, mode="auto") == (
                "xla", reason)
            monkeypatch.setattr(kreg, "backend", lambda: "tpu")
            assert kreg.decide(kd.op_type, key, mode="auto") == (
                "pallas", reason)
        finally:
            del kreg._KERNELS[kd.op_type]

    def test_lm_big_decode_attention_takes_the_kernel(self, on_tpu):
        """lm-big.backlog's decode call at 96 live (q (96, 16, 64), K/V
        (96, 2048, 16, 64), bfloat16): the gate abstains, which on the
        chip read 272.9 tokens/s with the kernel against 230.7 (PERF.md
        Findings (e))."""
        assert kreg.decide("DecodeAttention", _decode_key(96, 2048, 16, 64),
                           mode="auto", count=False) == (
            "pallas", "cost_model_uncertain")

    def test_lm_big_paged_attention_takes_the_kernel_by_a_gate_that_answers(
            self, on_tpu):
        """Every decode (Kq 1) and page-chunk prefill (Kq 64) bucket of
        chipbench/configs/lm-big.json: the pool read in place moves a
        seventh of the gathered, relaid view's bytes, so the gate answers
        — no abstention, and the configuration's ``force`` pin changes
        nothing. Where Pallas is interpreted the composition runs."""
        import json

        root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        with open(os.path.join(root, "chipbench", "configs",
                               "lm-big.json")) as f:
            kw = json.load(f)["program"]["model_kwargs"]
        keys = [_paged_key(b, kq, 16, 64, kw["page_len"],
                           kw["pages_per_seq"], kw["num_pages"] + 1)
                for kq, buckets in ((1, kw["decode_bucket_sizes"]),
                                    (kw["page_len"],
                                     kw["prefill_bucket_sizes"]))
                for b in buckets]
        assert len(keys) == 13
        for key in keys:
            assert kreg.decide("PagedDecodeAttention", key, mode="auto",
                               count=False) == ("pallas", "cost_model")
            assert kreg.decide("PagedDecodeAttention", key, mode="force",
                               count=False) == ("pallas", "forced")
            assert kreg._route(kreg._KERNELS["PagedDecodeAttention"], key,
                               "auto", "cpu") == ("xla",
                                                  "interpret_backend")

    def test_bert_base_s512_routes_as_the_chip_printed(self, on_tpu):
        """The cell's own files build its graph (batch 48, s512, 12
        heads x 64, hidden 768, 76 predictions a row, vocab 30522): every
        op with a kernel is one of the five types the chip's
        kernel_routing names, and each takes its kernel by a gate that
        answers, none through an abstention."""
        import json

        from chipbench.runners import train

        root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        with open(os.path.join(root, "chipbench", "configs",
                               "bert-base.json")) as f:
            config = json.load(f)
        with open(os.path.join(root, "chipbench", "traffic",
                               "pretrain-s512-b48.json")) as f:
            job = json.load(f)
        train.build(config, job)
        graph = stf.get_default_graph()
        recs = [r for r in kreg.routing_report(graph.get_operations(),
                                               mode="auto")
                if r["verdict"] != "no-kernel"]
        assert {r["type"] for r in recs} == {
            "FusedLayerNorm", "FlashAttention", "FusedSoftmaxXent",
            "SparseSoftmaxCrossEntropyWithLogits", "FusedAdamUpdate"}
        assert {(r["verdict"], r["reason"]) for r in recs} == {
            ("routed", "cost_model")}
        # the optimizer's live keys are one per (param, update) dtype
        # group, not the graph key's total
        params = {v._ref.op.attrs["var_name"]: v
                  for v in stf.global_variables()}
        adam, = [op for op in graph.get_operations()
                 if op.type == "FusedAdamUpdate"]
        groups = {}
        for names, udt in zip(adam.attrs["group_params"],
                              adam.attrs["group_ud"]):
            pdt = params[names[0]].dtype.base_dtype.name
            groups[pdt, udt] = sum(
                int(np.prod(params[n].shape.as_list())) for n in names)
        assert sorted(groups) == [("bfloat16", "float32"),
                                  ("float32", "float32")]
        assert 105e6 < sum(groups.values()) < 115e6
        for (pdt, udt), n in groups.items():
            assert kreg.decide("FusedAdamUpdate",
                               flat_group_key(n, pdt, udt),
                               mode="auto", count=False) == (
                "pallas", "cost_model")

    def test_deciding_writes_no_file(self, on_tpu, tmp_path, monkeypatch):
        from simple_tensorflow_tpu.compiler import aot

        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        monkeypatch.setattr(aot, "_persistent_cache_dir", str(tmp_path))
        assert aot.persistent_cache_dir() == str(tmp_path)
        for op_type, key in _RULE_KEYS.items():
            kreg.decide(op_type, key, mode="auto", count=False)
        assert os.listdir(tmp_path) == []


# ---------------------------------------------------------------------------
# seeded dropout reproducibility across implementation swaps
# ---------------------------------------------------------------------------

class TestSeededSwap:
    def _run_attention(self, mode):
        kreg.set_mode(mode)
        kreg.clear_decisions()
        stf.reset_default_graph()
        stf.set_random_seed(99)
        a = [np.random.RandomState(i).randn(1, 2, 16, 8).astype(np.float32)
             for i in range(3)]
        t = stf.nn.fused_attention(*[stf.constant(x) for x in a],
                                   dropout_rate=0.4)
        with stf.Session() as sess:
            return np.asarray(sess.run(t))

    def test_flash_dropout_mask_survives_impl_swap(self):
        # force = Pallas kernel, auto(cpu) = composed XLA: the
        # counter-based mask is identical, so the outputs agree to
        # float tolerance (a single differing mask element at rate 0.4
        # would diverge by O(1))
        o_force = self._run_attention("force")
        o_auto = self._run_attention("auto")
        np.testing.assert_allclose(o_force, o_auto, atol=5e-5, rtol=5e-5)

    def test_flash_dropout_folds_graph_seed(self):
        # same graph seed -> identical masks; different seed -> different
        o1 = self._run_attention("auto")
        o2 = self._run_attention("auto")
        np.testing.assert_array_equal(o1, o2)
        kreg.set_mode("auto")
        stf.reset_default_graph()
        stf.set_random_seed(100)
        a = [np.random.RandomState(i).randn(1, 2, 16, 8).astype(np.float32)
             for i in range(3)]
        t = stf.nn.fused_attention(*[stf.constant(x) for x in a],
                                   dropout_rate=0.4)
        with stf.Session() as sess:
            o3 = np.asarray(sess.run(t))
        assert not np.array_equal(o1, o3)

    def test_dropout_bias_residual_bitexact_across_modes(self):
        outs = {}
        for mode in ("force", "auto"):
            kreg.set_mode(mode)
            kreg.clear_decisions()
            stf.reset_default_graph()
            stf.set_random_seed(7)
            x = stf.constant(np.random.RandomState(0).randn(
                6, 10).astype(np.float32))
            r = stf.constant(np.random.RandomState(1).randn(
                6, 10).astype(np.float32))
            b = stf.constant(np.random.RandomState(2).randn(
                10).astype(np.float32))
            y = stf.nn.fused_bias_dropout_residual(x, r, b, rate=0.3)
            with stf.Session() as sess:
                outs[mode] = np.asarray(sess.run(y))
        np.testing.assert_array_equal(outs["force"], outs["auto"])

    def test_dropout_bias_residual_gradients(self):
        kreg.set_mode("force")
        stf.reset_default_graph()
        stf.set_random_seed(3)
        xv = np.random.RandomState(0).randn(5, 8).astype(np.float32)
        rv = np.random.RandomState(1).randn(5, 8).astype(np.float32)
        bv = np.random.RandomState(2).randn(8).astype(np.float32)
        x, r, b = (stf.constant(v) for v in (xv, rv, bv))
        y = stf.nn.fused_bias_dropout_residual(x, r, b, rate=0.25)
        loss = stf.reduce_sum(stf.square(y))
        gx, gr, gb = stf.gradients(loss, [x, r, b])
        with stf.Session() as sess:
            y_v, gx_v, gr_v, gb_v = (
                np.asarray(v) for v in sess.run([y, gx, gr, gb]))
        # dropout zeroed elements contribute zero dx; residual grad is
        # the full cotangent; dbias sums dx rows
        g = 2.0 * y_v
        np.testing.assert_allclose(gr_v, g, atol=1e-5)
        kept = gx_v != 0.0
        np.testing.assert_allclose(gx_v[kept], (g / (1 - 0.25))[kept],
                                   atol=1e-4, rtol=1e-4)
        np.testing.assert_allclose(gb_v, gx_v.sum(axis=0), atol=1e-4,
                                   rtol=1e-4)


# ---------------------------------------------------------------------------
# zoo force gate + offline report (graph_lint --kernels)
# ---------------------------------------------------------------------------

_ATTENTION_TYPES = {"FlashAttention", "FlashAttentionDropout",
                    "RingAttention"}


class TestRoutingReport:
    def test_transformer_zoo_routes_attention_under_force(self):
        from simple_tensorflow_tpu.models import transformer

        transformer.transformer_train_model(
            batch_size=2, src_len=8, tgt_len=8,
            cfg=transformer.TransformerConfig.tiny())
        ops = stf.get_default_graph().get_operations()
        recs = [r for r in kreg.routing_report(ops, mode="force")
                if r.get("type") in _ATTENTION_TYPES
                and r["verdict"] != "no-kernel"]
        assert recs, "transformer zoo graph lost its attention ops?"
        bad = [r for r in recs if r["verdict"] != "routed"]
        assert not bad, f"attention ops not routed under force: {bad}"

    def test_long_context_zoo_routes_attention_under_force(self):
        from simple_tensorflow_tpu.models import long_context

        long_context.lm_train_model(
            batch_size=1, seq_len=32,
            cfg=long_context.LongContextConfig.tiny())
        ops = stf.get_default_graph().get_operations()
        recs = [r for r in kreg.routing_report(ops, mode="force")
                if r.get("type") in _ATTENTION_TYPES
                and r["verdict"] != "no-kernel"]
        assert recs, "long_context zoo graph lost its attention ops?"
        bad = [r for r in recs if r["verdict"] != "routed"]
        assert not bad, f"attention ops not routed under force: {bad}"

    def test_graph_lint_kernels_cli(self, tmp_path):
        from simple_tensorflow_tpu.framework import graph_io
        from simple_tensorflow_tpu.models import transformer

        transformer.transformer_train_model(
            batch_size=2, src_len=8, tgt_len=8,
            cfg=transformer.TransformerConfig.tiny())
        gd_path = graph_io.write_graph(stf.get_default_graph(),
                                       str(tmp_path), "tf.json")
        env = dict(os.environ, JAX_PLATFORMS="cpu")
        out = subprocess.run(
            [sys.executable, "-m",
             "simple_tensorflow_tpu.tools.graph_lint", gd_path,
             "--kernels", "force", "--json",
             "--max-severity", "error"],
            capture_output=True, text=True, timeout=300, env=env)
        assert out.returncode == 0, out.stdout + out.stderr
        summaries = [json.loads(line)
                     for line in out.stdout.strip().splitlines()
                     if line.startswith("{")]
        kr = [s["kernel_routing"] for s in summaries
              if "kernel_routing" in s]
        assert kr, out.stdout[-2000:]
        table = kr[0]["by_op_type"]
        assert any(t in table for t in _ATTENTION_TYPES), table
        for t in _ATTENTION_TYPES & set(table):
            assert set(table[t]) == {"routed"}, table

    def test_statusz_snapshot_shape(self):
        snap = kreg.snapshot()
        assert snap["mode"] in ("off", "auto", "force")
        for k in ("routed", "fallback", "autotune_runs", "flash_tiles",
                  "paged_tiles", "kernels"):
            assert k in snap
        assert "measured" not in snap


# ---------------------------------------------------------------------------
# GSPMD cannot partition a Mosaic kernel (PR 21)
# ---------------------------------------------------------------------------

class TestMeshAutoPartitioned:
    """Under a multi-device mesh outside shard_map the step is
    partitioned by GSPMD, which refuses Mosaic kernels ("cannot be
    automatically partitioned"): on a TPU the registry must take the
    XLA lowering there, in every mode, and route normally again inside
    a shard_map body."""

    KEY = kreg.aval_key(np.zeros((8, 32), np.float32),
                        np.zeros((32,), np.float32),
                        np.zeros((32,), np.float32))

    @pytest.mark.parametrize("mode", ["off", "auto", "force"])
    def test_tpu_under_mesh_takes_xla(self, monkeypatch, mode):
        monkeypatch.setattr(kreg, "backend", lambda: "tpu")
        with kreg.activate(mode, auto_partitioned=True):
            assert kreg.decide("FusedLayerNorm", self.KEY, count=False) \
                == ("xla", "mesh_auto_partitioned")
            with kreg.activate(mode):          # a shard_map body
                impl, reason = kreg.decide("FusedLayerNorm", self.KEY,
                                           mode="force", count=False)
                assert (impl, reason) == ("pallas", "forced")
            assert kreg.decide("FusedLayerNorm", self.KEY, count=False) \
                == ("xla", "mesh_auto_partitioned")
        kreg.clear_decisions()

    def test_interpreted_kernels_partition_fine_off_tpu(self):
        with kreg.activate("force", auto_partitioned=True):
            assert kreg.decide("FusedLayerNorm", self.KEY, count=False) \
                == ("pallas", "forced")

    def test_session_lowering_sets_the_flag_from_the_mesh(self):
        from simple_tensorflow_tpu import parallel

        seen = []
        real = kreg.activate.__enter__

        def spy(self):
            seen.append(self._auto)
            return real(self)

        x = stf.placeholder(stf.float32, [8, 4], "x")
        y = stf.reduce_sum(x * 2.0)
        try:
            kreg.activate.__enter__ = spy
            with stf.Session() as sess:
                sess.run(y, {x: np.ones((8, 4), np.float32)})
            assert seen and not any(seen)
            del seen[:]
            with parallel.Mesh({"dp": 4}):
                with stf.Session() as sess:
                    sess.run(y * 3.0, {x: np.ones((8, 4), np.float32)})
            assert seen and all(seen)
        finally:
            kreg.activate.__enter__ = real
