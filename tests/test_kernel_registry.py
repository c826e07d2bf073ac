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
- ``off`` mode (STF_PALLAS=0) restores the pre-registry lowerings
  exactly: fused graph ops keep Pallas, optimizers rebuild the
  per-variable assign tail, trajectories match bit-for-bit;
- the measured autotune cache: verdicts override the static gate,
  measurements persist alongside the compile cache;
- the zoo force gate: transformer + long_context route their attention
  ops under ``force``;
- seeded dropout reproducibility across implementation swaps.
"""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

import simple_tensorflow_tpu as stf
from simple_tensorflow_tpu.kernels import registry as kreg


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
                  "interpret_backend", "cost_model",
                  "cost_model_uncertain", "autotune", "no_graph_key",
                  "unknown_shape"}


# ---------------------------------------------------------------------------
# modes
# ---------------------------------------------------------------------------

class TestModes:
    def test_env_kill_switch_parsing(self, monkeypatch):
        monkeypatch.delenv("STF_KERNELS", raising=False)
        monkeypatch.setenv("STF_PALLAS", "0")
        assert kreg._env_mode() == "off"
        monkeypatch.setenv("STF_PALLAS", "force")
        assert kreg._env_mode() == "force"
        monkeypatch.setenv("STF_PALLAS", "1")
        assert kreg._env_mode() == "auto"
        monkeypatch.delenv("STF_PALLAS")
        monkeypatch.setenv("STF_KERNELS", "off")
        assert kreg._env_mode() == "off"
        monkeypatch.delenv("STF_KERNELS")
        assert kreg._env_mode() == "auto"

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
    from simple_tensorflow_tpu.ops.pallas import flat_group_key

    n = int(rng.randint(1, 4000))
    key = flat_group_key(n, "float32", "float32")
    return ("FusedAdamUpdate" if kind == "adam"
            else "FusedMomentumUpdate"), key, True


def test_registry_fuzz_parity_and_counters():
    """Random (shape, dtype, mode) draws: the two lowerings agree on
    every eligible key, and the routed/fallback counters explain every
    decision (one increment each, reason from the documented set)."""
    import jax

    rng = np.random.RandomState(1234)
    for draw in range(18):
        op_type, key, exact = _draw_case(rng)
        mode = str(rng.choice(["off", "auto", "force"]))
        kd = kreg._KERNELS[op_type]
        if kd.eligible(key):
            continue  # ineligible draws covered by the mode tests
        args, kwargs = kd.make_case(key)
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
    # (STF_PALLAS=0 restores the per-variable assign tail exactly)
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
# autotune cache
# ---------------------------------------------------------------------------

class TestAutotune:
    def test_measured_verdict_overrides_static_gate(self):
        key = kreg.aval_key(np.zeros((8, 32), np.float32),
                            np.zeros((32,), np.float32),
                            np.zeros((32,), np.float32))
        bk = kreg.backend()
        # the CPU static gate says xla (interpret_backend); a measured
        # verdict must win anyway — auto never contradicts a measurement
        mkey = ("FusedLayerNorm", key, bk, kreg.device_kind())
        kreg._measured[mkey] = {
            "verdict": "pallas", "pallas_s": 1e-6, "xla_s": 1e-3}
        try:
            assert kreg.decide("FusedLayerNorm", key, mode="auto") == (
                "pallas", "autotune")
        finally:
            del kreg._measured[mkey]

    def test_verdict_of_another_device_kind_is_not_replayed(self):
        key = kreg.aval_key(np.zeros((8, 48), np.float32),
                            np.zeros((48,), np.float32),
                            np.zeros((48,), np.float32))
        mkey = ("FusedLayerNorm", key, kreg.backend(), "some other chip")
        kreg._measured[mkey] = {
            "verdict": "pallas", "pallas_s": 1e-6, "xla_s": 1e-3}
        try:
            assert kreg.decide("FusedLayerNorm", key, mode="auto") == (
                "xla", "interpret_backend")
        finally:
            del kreg._measured[mkey]
            kreg.clear_decisions()

    def test_failing_lowering_propagates_from_autotune(self):
        def broken(x):
            raise ValueError("mosaic says no")

        kd = kreg.register_kernel(
            "TestKernelBroken",
            impls={"pallas": broken, "xla": lambda x: x + x},
            legacy="xla",
            cost_gate=lambda key, bk: (None, "cost_model_uncertain"),
            make_case=lambda key: ((np.ones((4,), np.float32),), {}))
        key = kreg.aval_key(np.zeros((4,), np.float32))
        try:
            with pytest.raises(RuntimeError) as ei:
                kreg.decide("TestKernelBroken", key, mode="auto")
            msg = str(ei.value)
            assert "TestKernelBroken" in msg and "'pallas'" in msg
            assert isinstance(ei.value.__cause__, ValueError)
            # nothing was recorded as a verdict
            assert not [k for k in kreg.measured_verdicts()
                        if k[0] == "TestKernelBroken"]
        finally:
            del kreg._KERNELS[kd.op_type]

    def test_uncertain_gate_measures_once_and_caches(self):
        calls = []

        def gate(key, bk):
            return (None, "cost_model_uncertain")

        def case(key):
            return ((np.ones((4,), np.float32),), {})

        kd = kreg.register_kernel(
            "TestKernelUncertain",
            impls={"pallas": lambda x: x * 2.0, "xla": lambda x: x + x},
            legacy="xla", cost_gate=gate, make_case=case)
        try:
            n0 = kreg.metric_autotune_runs.get_cell(
                "TestKernelUncertain").value()
            key = kreg.aval_key(np.zeros((4,), np.float32))
            impl1, reason1 = kreg.decide("TestKernelUncertain", key,
                                         mode="auto")
            impl2, reason2 = kreg.decide("TestKernelUncertain", key,
                                         mode="auto")
            assert reason1 == reason2 == "autotune"
            assert impl1 == impl2
            n1 = kreg.metric_autotune_runs.get_cell(
                "TestKernelUncertain").value()
            assert n1 == n0 + 1  # measured exactly once, then cached
            mkey = ("TestKernelUncertain", key, kreg.backend(),
                    kreg.device_kind())
            assert mkey in kreg.measured_verdicts()
        finally:
            del kreg._KERNELS["TestKernelUncertain"]
            kreg._measured.pop(mkey, None)

    def test_persistence_roundtrip(self, tmp_path, monkeypatch):
        from simple_tensorflow_tpu.compiler import aot

        monkeypatch.setattr(aot, "_persistent_cache_dir", str(tmp_path))
        monkeypatch.setattr(kreg, "_measured_loaded_from", None)
        key = kreg.aval_key(np.zeros((3, 3), np.float32), probe=True)
        cache_key = ("FusedLayerNorm", key, "cpu", "cpu")
        kreg._measured[cache_key] = {"verdict": "pallas",
                                     "pallas_s": 1e-6, "xla_s": 1e-3}
        try:
            kreg._persist()
            assert (tmp_path / "stf_kernel_autotune.json").exists()
            del kreg._measured[cache_key]
            kreg._load_persisted()
            assert kreg._measured[cache_key]["verdict"] == "pallas"
        finally:
            kreg._measured.pop(cache_key, None)


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
                  "kernels"):
            assert k in snap


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
