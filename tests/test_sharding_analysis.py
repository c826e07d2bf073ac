"""stf.analysis.sharding test matrix (ISSUE 6).

- unit tests per propagation rule (abstract {axis: size} meshes — no
  devices, no Session),
- lint rules (replicated-large-tensor / resharding-hotspot /
  mesh-axis-unused / uneven-shard),
- match_partition_rules (the regex rule -> PartitionSpec seeder),
- Session wiring (per-plan report, RunMetadata.predicted_collectives,
  init plans skipped),
- GOLDEN tests on the 8-way virtual mesh: jit-lowered train steps where
  the analyzer's predicted output shardings must match JAX's committed
  shardings and predicted collective bytes must track XLA's harvested
  cost,
- a fuzz test over random graphs: analyzer-predicted replication must
  imply XLA commits a replicated output sharding (the analyzer may be
  conservative, never optimistic),
- the graph_lint CLI acceptance path (--json --mesh --rules
  --max-severity on a deliberately mis-sharded GraphDef).
"""

import json
import subprocess
import sys

import numpy as np
import pytest

import simple_tensorflow_tpu as stf
from simple_tensorflow_tpu import analysis, parallel
from simple_tensorflow_tpu.analysis import sharding as shard_mod
from simple_tensorflow_tpu.parallel import P


@pytest.fixture(autouse=True)
def fresh_graph():
    stf.reset_default_graph()
    yield
    stf.reset_default_graph()


def _analyze(mesh, seed_specs=None, fetches=None, graph=None, **kw):
    return analysis.analyze_sharding(
        graph=graph or stf.get_default_graph(), mesh=mesh,
        seed_specs=seed_specs, fetches=fetches, **kw)


def _edges(rep, kind=None):
    es = rep.collective_edges()
    if kind is not None:
        es = [e for e in es if e.kind == kind]
    return es


def _codes(rep):
    return {d.code for d in rep.diagnostics}


DP8 = {"dp": 8}


# ---------------------------------------------------------------------------
# spec algebra
# ---------------------------------------------------------------------------

class TestSpecAlgebra:
    def test_normalize_and_display(self):
        n = shard_mod.normalize_spec(P("dp", None), 3)
        assert n == (("dp",), (), ())
        assert shard_mod.to_partition_spec(n) == ("dp", None, None)
        assert shard_mod.format_spec(n) == "P(dp, None, None)"
        assert shard_mod.normalize_spec(None, 2) == ((), ())
        assert shard_mod.normalize_spec(("dp",), 1) == (("dp",),)
        # multi-axis entry
        assert shard_mod.normalize_spec((("dp", "tp"),), 1) == \
            (("dp", "tp"),)

    def test_dedupe_axes_first_occurrence_wins(self):
        assert shard_mod._dedupe_axes((("dp",), ("dp",), ())) == \
            (("dp",), (), ())

    def test_shard_factor(self):
        axes = {"dp": 8, "tp": 4}
        assert shard_mod.shard_factor((("dp",), ("tp",)), axes) == 32
        assert shard_mod.shard_factor(((), ()), axes) == 1
        assert shard_mod.shard_factor(None, axes) == 1

    def test_parse_mesh_arg(self):
        assert shard_mod.parse_mesh_arg("8") == {"dp": 8}
        assert shard_mod.parse_mesh_arg("2x4") == {"dp": 2, "tp": 4}
        assert shard_mod.parse_mesh_arg("dp=2,tp=4") == {"dp": 2,
                                                        "tp": 4}
        with pytest.raises(ValueError):
            shard_mod.parse_mesh_arg("2x2x2x2x2")


# ---------------------------------------------------------------------------
# propagation rules (abstract mesh, no devices)
# ---------------------------------------------------------------------------

class TestPropagationRules:
    def test_elementwise_broadcast_carries_sharding(self):
        x = stf.placeholder(stf.float32, [16, 8], name="x")
        b = stf.placeholder(stf.float32, [8], name="b")
        y = x + b
        rep = _analyze(DP8, seed_specs={"x": ("dp", None)})
        assert rep.spec_of(y) == ("dp", None)
        assert _edges(rep) == []  # broadcast needs no comms

    def test_elementwise_conflict_joins_replicated(self):
        x = stf.placeholder(stf.float32, [16, 16], name="x")
        y = stf.placeholder(stf.float32, [16, 16], name="y")
        z = x + y
        rep = _analyze({"dp": 4, "tp": 2},
                       seed_specs={"x": ("dp", None),
                                   "y": ("tp", None)})
        assert rep.spec_of(z) == (None, None)
        assert "sharding/conflict" in _codes(rep)

    def test_matmul_batch_sharded(self):
        x = stf.placeholder(stf.float32, [16, 8], name="x")
        w = stf.placeholder(stf.float32, [8, 4], name="w")
        y = stf.matmul(x, w)
        rep = _analyze(DP8, seed_specs={"x": ("dp", None)})
        assert rep.spec_of(y) == ("dp", None)
        assert _edges(rep) == []

    def test_matmul_contracted_sharded_implies_allreduce(self):
        x = stf.placeholder(stf.float32, [16, 8], name="x")
        w = stf.placeholder(stf.float32, [8, 4], name="w")
        y = stf.matmul(x, w)
        rep = _analyze(DP8, seed_specs={"x": (None, "dp"),
                                        "w": ("dp", None)})
        ar = _edges(rep, "all-reduce")
        assert len(ar) == 1
        assert ar[0].axes == ("dp",)
        assert ar[0].nbytes == 16 * 4 * 4  # output replicated

    def test_matmul_tp_output_sharding(self):
        x = stf.placeholder(stf.float32, [16, 8], name="x")
        w = stf.placeholder(stf.float32, [8, 32], name="w")
        y = stf.matmul(x, w)
        rep = _analyze({"dp": 4, "tp": 2},
                       seed_specs={"x": ("dp", None),
                                   "w": (None, "tp")})
        assert rep.spec_of(y) == ("dp", "tp")
        assert _edges(rep) == []

    def test_reduce_over_sharded_dim(self):
        x = stf.placeholder(stf.float32, [16, 8], name="x")
        s = stf.reduce_sum(x, axis=0)
        rep = _analyze(DP8, seed_specs={"x": ("dp", None)})
        assert rep.spec_of(s) == (None,)
        ar = _edges(rep, "all-reduce")
        assert len(ar) == 1 and ar[0].nbytes == 8 * 4

    def test_reduce_over_unsharded_dim_keeps_sharding(self):
        x = stf.placeholder(stf.float32, [16, 8], name="x")
        s = stf.reduce_sum(x, axis=1)
        rep = _analyze(DP8, seed_specs={"x": ("dp", None)})
        assert rep.spec_of(s) == ("dp",)
        assert _edges(rep) == []

    def test_transpose_permutes_spec(self):
        x = stf.placeholder(stf.float32, [16, 8], name="x")
        t = stf.transpose(x)
        rep = _analyze(DP8, seed_specs={"x": ("dp", None)})
        assert rep.spec_of(t) == (None, "dp")

    def test_reshape_carries_outer_factor(self):
        x = stf.placeholder(stf.float32, [16, 8], name="x")
        r = stf.reshape(x, [16, 2, 4])
        rep = _analyze(DP8, seed_specs={"x": ("dp", None)})
        assert rep.spec_of(r) == ("dp", None, None)

    def test_reshape_murky_gathers(self):
        x = stf.placeholder(stf.float32, [16, 8], name="x")
        r = stf.reshape(x, [8, 16])
        rep = _analyze(DP8, seed_specs={"x": (None, "dp")})
        assert rep.spec_of(r) == (None, None)
        assert "sharding/reshape-gather" in _codes(rep)
        assert _edges(rep, "all-gather")

    def test_concat_along_sharded_dim_gathers(self):
        x = stf.placeholder(stf.float32, [16, 8], name="x")
        y = stf.placeholder(stf.float32, [16, 8], name="y")
        c = stf.concat([x, y], axis=0)
        rep = _analyze(DP8, seed_specs={"x": ("dp", None),
                                        "y": ("dp", None)})
        assert rep.spec_of(c) == (None, None)
        assert len(_edges(rep, "all-gather")) == 2

    def test_concat_along_other_dim_keeps_sharding(self):
        x = stf.placeholder(stf.float32, [16, 8], name="x")
        y = stf.placeholder(stf.float32, [16, 8], name="y")
        c = stf.concat([x, y], axis=1)
        rep = _analyze(DP8, seed_specs={"x": ("dp", None),
                                        "y": ("dp", None)})
        assert rep.spec_of(c) == ("dp", None)
        assert _edges(rep) == []

    def test_gather_vocab_sharded_implies_allreduce(self):
        emb = stf.placeholder(stf.float32, [64, 16], name="emb")
        ids = stf.placeholder(stf.int32, [8], name="ids")
        g = stf.gather(emb, ids)
        rep = _analyze(DP8, seed_specs={"emb": ("dp", None)})
        assert rep.spec_of(g) == (None, None)
        assert _edges(rep, "all-reduce")

    def test_conv_batch_passthrough_spatial_gathered(self):
        x = stf.placeholder(stf.float32, [8, 8, 8, 3], name="x")
        w = stf.placeholder(stf.float32, [3, 3, 3, 4], name="w")
        y = stf.nn.conv2d(x, w, strides=[1, 1, 1, 1], padding="SAME")
        rep = _analyze(DP8, seed_specs={"x": ("dp", None, None, None)})
        assert rep.spec_of(y) == ("dp", None, None, None)
        assert _edges(rep) == []
        # sharded spatial dim is consumed gathered
        stf.reset_default_graph()
        x = stf.placeholder(stf.float32, [8, 8, 8, 3], name="x")
        w = stf.placeholder(stf.float32, [3, 3, 3, 4], name="w")
        y = stf.nn.conv2d(x, w, strides=[1, 1, 1, 1], padding="SAME")
        rep = _analyze(DP8, seed_specs={"x": (None, "dp", None, None)})
        assert _edges(rep, "all-gather")

    def test_softmax_sharded_class_dim_small_allreduce(self):
        x = stf.placeholder(stf.float32, [16, 32], name="x")
        s = stf.nn.softmax(x)
        rep = _analyze(DP8, seed_specs={"x": (None, "dp")})
        assert rep.spec_of(s) == (None, "dp")
        ar = _edges(rep, "all-reduce")
        assert len(ar) == 1
        assert ar[0].nbytes < 16 * 32 * 4  # stats, not the tensor

    def test_slice_changed_dim_loses_sharding(self):
        x = stf.placeholder(stf.float32, [16, 8], name="x")
        s = x[:8]
        rep = _analyze(DP8, seed_specs={"x": ("dp", None)})
        assert rep.spec_of(s) == (None, None)
        assert _edges(rep, "all-gather")

    def test_stack_unstack(self):
        x = stf.placeholder(stf.float32, [16, 8], name="x")
        y = stf.placeholder(stf.float32, [16, 8], name="y")
        st = stf.stack([x, y])
        rep = _analyze(DP8, seed_specs={"x": ("dp", None),
                                        "y": ("dp", None)})
        assert rep.spec_of(st) == (None, "dp", None)

    def test_assign_commits_variable_sharding(self):
        v = stf.get_variable("w", [16, 8],
                             initializer=stf.zeros_initializer())
        x = stf.placeholder(stf.float32, [16, 8], name="x")
        a = stf.assign(v, x)
        rep = _analyze(DP8, seed_specs={"w": ("dp", None)})
        assert rep.spec_of(a) == ("dp", None)
        # replicated value resharding into the sharded variable is a
        # local slice (no wire traffic), not a gather
        assert _edges(rep, "slice") or _edges(rep) == []

    def test_einsum_contraction(self):
        a = stf.placeholder(stf.float32, [16, 8], name="a")
        b = stf.placeholder(stf.float32, [8, 4], name="b")
        y = stf.einsum("ij,jk->ik", a, b)
        rep = _analyze(DP8, seed_specs={"a": (None, "dp"),
                                        "b": ("dp", None)})
        assert _edges(rep, "all-reduce")

    def test_sharding_constraint_seeds_both_directions(self):
        x = stf.placeholder(stf.float32, [16, 8], name="x")
        y = x * 2.0
        z = parallel.with_sharding_constraint(y, "dp", None)
        w = z + 1.0
        rep = _analyze(DP8)
        assert rep.spec_of(z) == ("dp", None)
        assert rep.spec_of(w) == ("dp", None)     # forward
        assert rep.spec_of(x) == ("dp", None)     # backward sweep

    def test_no_rule_conservative_gather_and_note(self):
        from simple_tensorflow_tpu.framework import op_registry

        if not op_registry.is_registered("ShardingTestRulelessOp"):
            op_registry.register("ShardingTestRulelessOp",
                                 lower=lambda ctx, op, inputs: inputs)
        g = stf.get_default_graph()
        x = stf.placeholder(stf.float32, [16, 8], name="x")
        op = g.create_op("ShardingTestRulelessOp", [x], name="unk",
                         output_specs=[(x.shape, x.dtype)])
        rep = _analyze(DP8, seed_specs={"x": ("dp", None)})
        assert "sharding/no-rule" in _codes(rep)
        assert rep.spec_of(op.outputs[0]) == (None, None)
        assert _edges(rep, "all-gather")

    def test_rule_registered_alongside_op_registry(self):
        from simple_tensorflow_tpu.framework import op_registry

        assert op_registry.sharding_rule("MatMul") is not None
        assert op_registry.sharding_rule("Conv2D") is not None
        assert op_registry.sharding_rule("NoSuchOpType") is None


class TestControlFlow:
    def test_while_body_reshard_is_trip_weighted_hotspot(self):
        v = stf.get_variable("w", [64, 64],
                             initializer=stf.zeros_initializer())
        x = stf.placeholder(stf.float32, [8, 64], name="x")

        def cond(i, y):
            return stf.less(i, 8)

        def body(i, y):
            return i + 1, stf.matmul(y, v.value())

        _, yn = stf.while_loop(cond, body, [stf.constant(0), x],
                               maximum_iterations=8)
        rep = _analyze(DP8, seed_specs={"w": ("dp", None)})
        gathers = [e for e in _edges(rep) if e.in_loop]
        assert gathers, "expected an in-loop collective edge"
        assert all(e.trip == 8 for e in gathers)
        assert "lint/resharding-hotspot" in _codes(rep)

    def test_nonconverging_carry_records_edges_once(self):
        """Regression: a carry whose spec changes during the fixpoint
        (round 2 re-analyzes the body) must not double-record the
        body's collective edges — only the final sweep records."""
        x = stf.placeholder(stf.float32, [16, 8], name="x")

        def cond(i, y):
            return stf.less(i, 4)

        def body(i, y):
            y2 = parallel.with_sharding_constraint(y, "dp", None)
            s = stf.reduce_sum(y2, axis=0, keepdims=True)
            return i + 1, y2 + s

        _, yn = stf.while_loop(cond, body, [stf.constant(0), x],
                               maximum_iterations=4)
        rep = _analyze(DP8)  # carry: replicated -> dp after round 1
        assert rep.spec_of(yn) == ("dp", None)
        ar = [e for e in _edges(rep, "all-reduce") if e.in_loop]
        assert len(ar) == 1, [e.to_dict() for e in ar]
        assert ar[0].trip == 4

    def test_scan_carry_fixpoint(self):
        xs = stf.placeholder(stf.float32, [4, 16, 8], name="xs")
        init = stf.placeholder(stf.float32, [16, 8], name="init")
        from simple_tensorflow_tpu.ops import functional_ops

        out = functional_ops.scan(lambda c, e: c + e, xs,
                                  initializer=init)
        rep = _analyze(DP8, seed_specs={"init": ("dp", None),
                                        "xs": (None, "dp", None)})
        # stacked output regains the leading iteration dim
        assert rep.spec_of(out) == (None, "dp", None)
        assert _edges(rep) == []

    def test_cond_branches_join(self):
        p = stf.placeholder(stf.bool, [], name="p")
        x = stf.placeholder(stf.float32, [16, 8], name="x")
        y = stf.cond(p, lambda: x * 2.0, lambda: x + 1.0)
        rep = _analyze(DP8, seed_specs={"x": ("dp", None)})
        assert rep.spec_of(y) == ("dp", None)


class TestLintRules:
    def test_replicated_large_tensor(self):
        stf.get_variable("big", [1024, 512],
                         initializer=stf.zeros_initializer())  # 2 MiB
        stf.get_variable("small", [4, 4],
                         initializer=stf.zeros_initializer())
        rep = _analyze(DP8)
        msgs = [d for d in rep.diagnostics
                if d.code == "lint/replicated-large-tensor"]
        assert len(msgs) == 1
        assert "big" in msgs[0].message

    def test_replicated_large_tensor_quiet_when_sharded(self):
        stf.get_variable("big", [1024, 512],
                         initializer=stf.zeros_initializer())
        rep = _analyze(DP8, seed_specs={"big": ("dp", None)})
        assert "lint/replicated-large-tensor" not in _codes(rep)

    def test_replicated_large_tensor_quiet_on_one_device(self):
        stf.get_variable("big", [1024, 512],
                         initializer=stf.zeros_initializer())
        rep = _analyze({"dp": 1})
        assert "lint/replicated-large-tensor" not in _codes(rep)

    def test_mesh_axis_unused(self):
        x = stf.placeholder(stf.float32, [16, 8], name="x")
        _ = x * 2.0
        rep = _analyze({"dp": 4, "tp": 2}, seed_specs={"x": ("dp",
                                                             None)})
        msgs = [d for d in rep.diagnostics
                if d.code == "lint/mesh-axis-unused"]
        assert len(msgs) == 1 and "'tp'" in msgs[0].message

    def test_uneven_shard(self):
        x = stf.placeholder(stf.float32, [12, 8], name="x")  # 12 % 8
        _ = x * 2.0
        rep = _analyze(DP8, seed_specs={"x": ("dp", None)})
        msgs = [d for d in rep.diagnostics
                if d.code == "lint/uneven-shard"]
        assert msgs and "padding" in msgs[0].message


class TestMatchPartitionRules:
    def _vars(self):
        a = stf.get_variable("encoder/attn/wq", [64, 64],
                             initializer=stf.zeros_initializer())
        b = stf.get_variable("encoder/mlp/kernel", [64, 256],
                             initializer=stf.zeros_initializer())
        c = stf.get_variable("global_step", [],
                             initializer=stf.zeros_initializer(),
                             dtype=stf.int64)
        return a, b, c

    def test_first_match_wins_and_scalars_replicate(self):
        self._vars()
        specs = parallel.match_partition_rules(
            [(r"attn/w[qkv]", P(None, "tp")),
             (r"mlp/kernel", P(None, "tp")),
             (r".*", P())])
        assert specs["encoder/attn/wq"] == P(None, "tp")
        assert specs["encoder/mlp/kernel"] == P(None, "tp")
        assert specs["global_step"] == P()

    def test_on_missing_modes(self):
        self._vars()
        with pytest.raises(ValueError, match="no rule matches"):
            parallel.match_partition_rules([(r"attn", P(None, "tp"))],
                                           on_missing="error")
        out = parallel.match_partition_rules(
            [(r"attn/w[qkv]", P(None, "tp"))], on_missing="skip")
        assert "encoder/mlp/kernel" not in out
        out = parallel.match_partition_rules(
            [(r"attn/w[qkv]", P(None, "tp"))], on_missing="replicate")
        assert out["encoder/mlp/kernel"] == P()

    def test_apply_commits_to_variables(self):
        a, b, _ = self._vars()
        parallel.match_partition_rules(
            [(r"attn/w[qkv]", P(None, "tp"))], apply=True)
        assert tuple(a.sharding) == (None, "tp")

    def test_rules_feed_analyzer_as_seeds(self):
        a, b, _ = self._vars()
        x = stf.placeholder(stf.float32, [16, 64], name="x")
        y = stf.matmul(x, a.value())
        specs = parallel.match_partition_rules(
            [(r"attn/w[qkv]", P(None, "tp"))])
        rep = _analyze({"dp": 4, "tp": 2}, seed_specs=specs)
        assert rep.spec_of(y) == (None, "tp")


# ---------------------------------------------------------------------------
# Session wiring + golden committed shardings (8-device virtual mesh)
# ---------------------------------------------------------------------------

def _traced_run(sess, fetches, feed):
    opts = stf.RunOptions(trace_level=stf.RunOptions.SOFTWARE_TRACE)
    md = stf.RunMetadata()
    vals = sess.run(fetches, feed_dict=feed, options=opts,
                    run_metadata=md)
    # the analysis overlaps compile on a worker thread; join for asserts
    steps = [s for s in sess._cache.values()
             if s.join_sharding() is not None]
    assert steps, "no plan carried a sharding report"
    return vals, md, steps[-1]


def _assert_fetches_match_committed(step, mesh):
    """Analyzer-predicted device-fetch specs == JAX committed output
    shardings of the AOT-compiled executable."""
    import jax

    if step.compiled is None:
        pytest.skip("AOT compile path unavailable")
    fetch_shardings = step.compiled.output_shardings[0]
    rep = step.sharding_report
    checked = 0
    for t, sh in zip(step.device_fetches, fetch_shardings):
        pred = rep.spec_of(t)
        if pred is None:
            continue
        expected = jax.sharding.NamedSharding(
            mesh.jax_mesh, jax.sharding.PartitionSpec(*pred))
        assert sh.is_equivalent_to(expected, len(pred)), (
            f"{t.name}: predicted {pred}, XLA committed {sh}")
        checked += 1
    return checked


class TestSessionWiring:
    def test_plan_report_and_run_metadata(self):
        mesh = parallel.Mesh(DP8)
        with mesh:
            x = stf.placeholder(stf.float32, [16, 8], name="x")
            parallel.shard_feed(x, "dp")
            w = stf.get_variable("w", [8, 4],
                                 initializer=stf.zeros_initializer())
            loss = stf.reduce_mean(stf.matmul(x, w))
            with stf.Session() as sess:
                sess.run(stf.global_variables_initializer())
                # the initializer plan must NOT be sharding-analyzed
                # (no feeds, nothing sharded: every diagnostic would be
                # noise)
                assert all(s.sharding_report is None
                           and s.sharding_thread is None
                           for s in sess._cache.values())
                _, md, step = _traced_run(
                    sess, loss,
                    {x: np.ones((16, 8), np.float32)})
                rep = step.sharding_report
                assert rep.mesh_axes == {"dp": 8}
                pc = md.cost_graph["predicted_collectives"]
                assert pc["total_bytes"] == rep.total_collective_bytes()
                assert pc["per_op"]
                # harvested comparator present under SOFTWARE_TRACE
                assert "collective_bytes" in md.cost_graph

    def test_no_mesh_no_report(self):
        x = stf.placeholder(stf.float32, [4], name="x")
        y = x * 2.0
        with stf.Session() as sess:
            sess.run(y, feed_dict={x: np.ones(4, np.float32)})
            assert all(s.sharding_report is None
                       for s in sess._cache.values())

    def test_sharding_metrics_counted(self):
        from simple_tensorflow_tpu import monitoring

        before = monitoring.get_metric(
            "/stf/analysis/sharding_collectives")
        n0 = sum(before.snapshot()["cells"].values()) if before else 0
        mesh = parallel.Mesh(DP8)
        with mesh:
            x = stf.placeholder(stf.float32, [16, 8], name="x")
            parallel.shard_feed(x, "dp")
            s = stf.reduce_sum(x, axis=0)
            with stf.Session() as sess:
                sess.run(s, feed_dict={x: np.ones((16, 8),
                                                  np.float32)})
                for st in sess._cache.values():
                    st.join_sharding()
        after = monitoring.get_metric(
            "/stf/analysis/sharding_collectives")
        assert sum(after.snapshot()["cells"].values()) > n0


class TestGoldenCommitted:
    def test_mlp_dp8_train_step(self):
        """dp8 MLP: predicted fetch shardings match committed; predicted
        collective bytes match XLA's harvested bytes (exactly: this
        program's only collectives are the loss + gradient syncs)."""
        mesh = parallel.Mesh(DP8)
        rng = np.random.RandomState(0)
        with mesh:
            x = stf.placeholder(stf.float32, [16, 8], name="x")
            y = stf.placeholder(stf.float32, [16, 4], name="y")
            parallel.shard_feed(x, "dp")
            parallel.shard_feed(y, "dp")
            w1 = stf.get_variable(
                "w1", [8, 32], initializer=stf.zeros_initializer())
            w2 = stf.get_variable(
                "w2", [32, 4], initializer=stf.zeros_initializer())
            h = stf.nn.relu(stf.matmul(x, w1))
            pred = stf.matmul(h, w2)
            loss = stf.reduce_mean(stf.square(pred - y))
            opt = stf.train.GradientDescentOptimizer(0.1)
            train_op = opt.minimize(loss)
            with stf.Session() as sess:
                sess.run(stf.global_variables_initializer())
                feed = {x: rng.randn(16, 8).astype(np.float32),
                        y: rng.randn(16, 4).astype(np.float32)}
                _, md, step = _traced_run(sess, [train_op, loss], feed)
                assert _assert_fetches_match_committed(step, mesh) >= 1
                predicted = step.sharding_report \
                    .total_collective_bytes()
                harvested = md.cost_graph.get(
                    "collective_bytes", {}).get("total")
                if harvested:  # backend exposed HLO text
                    assert predicted == pytest.approx(harvested,
                                                      rel=0.25)

    def test_transformer_dp8_train_step(self):
        """Golden satellite: a jit-lowered transformer train step on the
        8-way mesh. Committed output shardings match; the all-reduce
        prediction (gradient/batch-stat sync, the dominant wire cost)
        tracks XLA within 25%. (Total bytes are NOT compared here: XLA
        all-gathers the scan-stacked residuals on its dynamic-slice
        layout choice.)
        """
        from simple_tensorflow_tpu.models import transformer as tr

        mesh = parallel.Mesh(DP8)
        rng = np.random.RandomState(0)
        with mesh:
            cfg = tr.TransformerConfig.tiny()
            m = tr.transformer_train_model(batch_size=8, src_len=8,
                                           tgt_len=8, cfg=cfg,
                                           compute_dtype=stf.float32)
            for k in ("src_ids", "tgt_in", "tgt_out"):
                parallel.shard_feed(m[k], "dp")
            feed = {
                m["src_ids"]: rng.randint(
                    1, 30, (8, 8)).astype(np.int32),
                m["tgt_in"]: rng.randint(
                    1, 30, (8, 8)).astype(np.int32),
                m["tgt_out"]: rng.randint(
                    1, 30, (8, 8)).astype(np.int32)}
            with stf.Session() as sess:
                sess.run(stf.global_variables_initializer())
                _, md, step = _traced_run(
                    sess, [m["train_op"], m["loss"]], feed)
                rep = step.sharding_report
                # every zoo op type must have a rule by now: the fused
                # kernels were the last gaps (FlashAttention &co)
                assert "sharding/no-rule" not in _codes(rep)
                assert _assert_fetches_match_committed(step, mesh) >= 1
                harvested = md.cost_graph.get("collective_bytes", {})
                if harvested.get("all-reduce"):
                    assert rep.bytes_by_kind().get("all-reduce", 0) == \
                        pytest.approx(harvested["all-reduce"], rel=0.25)


class TestGoldenResnet:
    def test_resnet_dp8_train_step(self):
        """Golden satellite: the resnet50 train step on the 8-way mesh
        (the bench config at reduced batch). Committed fetch shardings
        match the prediction and total predicted collective bytes track
        the harvested HLO bytes within 25% (scan-free model: the total
        IS comparable; the bench row pins the full-size config)."""
        from simple_tensorflow_tpu.models import resnet

        mesh = parallel.Mesh(DP8)
        with mesh:
            m = resnet.resnet50_train_model(batch_size=8, image_size=32,
                                            num_classes=10)
            parallel.shard_feed(m["images"], "dp")
            parallel.shard_feed(m["labels"], "dp")
            xv, yv = resnet.synthetic_imagenet(8, 32, dtype=np.float32)
            feed = {m["images"]: xv, m["labels"]: yv}
            with stf.Session() as sess:
                sess.run(stf.global_variables_initializer())
                _, md, step = _traced_run(
                    sess, [m["train_op"], m["loss"]], feed)
                rep = step.sharding_report
                assert "sharding/no-rule" not in _codes(rep)
                assert _assert_fetches_match_committed(step, mesh) >= 1
                harvested = md.cost_graph.get(
                    "collective_bytes", {}).get("total")
                if harvested:
                    assert rep.total_collective_bytes() == \
                        pytest.approx(harvested, rel=0.25)


class TestFuzzReplicationSound:
    """Random graphs: wherever the analyzer predicts a REPLICATED device
    fetch, XLA must commit a replicated output sharding. (The analyzer
    is allowed to be conservative — predicting replicated where XLA
    keeps a sharding would fail the golden tests' exact checks but not
    this soundness property; predicting sharded where XLA replicates is
    what this hunts.)"""

    def _random_graph(self, rng):
        x = stf.placeholder(stf.float32, [16, 8], name="x")
        parallel.shard_feed(x, "dp")
        vals = [x]
        for i in range(rng.randint(2, 6)):
            t = vals[rng.randint(len(vals))]
            k = rng.randint(6)
            if k == 0:
                vals.append(t * 2.0 + 1.0)
            elif k == 1:
                vals.append(stf.nn.relu(t))
            elif k == 2 and t.shape.rank == 2:
                w = stf.constant(
                    rng.randn(int(t.shape[1]), 8).astype(np.float32))
                vals.append(stf.matmul(t, w))
            elif k == 3 and t.shape.rank == 2:
                vals.append(stf.reduce_sum(t, axis=rng.randint(2)))
            elif k == 4 and t.shape.rank == 2:
                vals.append(stf.transpose(t))
            else:
                vals.append(stf.exp(-t))
        # always end host-small so the program has a fetchable scalar
        vals.append(stf.reduce_mean(vals[-1]))
        return x, vals[-1], vals[len(vals) // 2]

    @pytest.mark.parametrize("seed", range(6))
    def test_predicted_replication_is_sound(self, seed):
        rng = np.random.RandomState(seed)
        mesh = parallel.Mesh(DP8)
        with mesh:
            x, out, mid = self._random_graph(rng)
            fetches = [out]
            if mid.shape.rank is not None:
                fetches.append(mid)
            with stf.Session() as sess:
                _, _md, step = _traced_run(
                    sess, fetches,
                    {x: rng.randn(16, 8).astype(np.float32)})
                if step.compiled is None:
                    pytest.skip("AOT compile path unavailable")
                rep = step.sharding_report
                fetch_shardings = step.compiled.output_shardings[0]
                for t, sh in zip(step.device_fetches, fetch_shardings):
                    pred = rep.spec_of(t)
                    if pred is not None and all(e is None
                                                for e in pred):
                        assert sh.is_fully_replicated, (
                            f"{t.name}: analyzer says replicated, XLA "
                            f"committed {sh}")


# ---------------------------------------------------------------------------
# graph_lint CLI (acceptance criterion)
# ---------------------------------------------------------------------------

def _missharded_graphdef(tmp_path):
    """Deliberately mis-sharded example: a large replicated embedding
    (never matched by the rules) + a while body that re-gathers a
    rule-sharded weight every iteration."""
    from simple_tensorflow_tpu.framework import graph_io

    g = stf.Graph()
    with g.as_default():
        stf.get_variable("embeddings", [1024, 512],
                         initializer=stf.zeros_initializer())
        v = stf.get_variable("mlp/kernel", [512, 512],
                             initializer=stf.zeros_initializer())
        x = stf.placeholder(stf.float32, [64, 512], name="x")

        def cond(i, y):
            return stf.less(i, 8)

        def body(i, y):
            return i + 1, stf.matmul(y, v.value())

        _, yn = stf.while_loop(cond, body, [stf.constant(0), x],
                               maximum_iterations=8)
        stf.reduce_sum(yn, name="loss")
    gd = graph_io.graph_to_graphdef(g)
    gpath = tmp_path / "missharded.json"
    gpath.write_text(json.dumps(gd))
    rpath = tmp_path / "rules.json"
    rpath.write_text(json.dumps([["mlp/.*", ["dp", None]]]))
    return gpath, rpath


class TestGraphLintCLI:
    def test_json_mesh_rules_and_exit_code(self, tmp_path):
        from simple_tensorflow_tpu.tools import graph_lint

        gpath, rpath = _missharded_graphdef(tmp_path)
        argv = [str(gpath), "--json", "--mesh", "8",
                "--rules", str(rpath), "--fetch", "loss"]

        import io
        from contextlib import redirect_stdout

        buf = io.StringIO()
        with redirect_stdout(buf):
            rc = graph_lint.main(argv)  # default gate: errors only
        lines = [json.loads(line) for line in
                 buf.getvalue().strip().splitlines()]
        codes = {d.get("code") for d in lines if "code" in d}
        assert "lint/replicated-large-tensor" in codes
        assert "lint/resharding-hotspot" in codes
        assert rc == 0  # warnings alone don't fail the default gate

        summary = [d for d in lines if "summary" in d]
        assert summary, "--json must emit a trailing summary record"
        s = summary[0]["summary"]
        assert s["total_collective_bytes"] > 0
        assert "all-gather" in s["bytes_by_kind"]

        buf = io.StringIO()
        with redirect_stdout(buf):
            rc = graph_lint.main(argv + ["--max-severity", "warning"])
        assert rc == 1  # sharding hygiene gate trips on warnings

    def test_rules_require_mesh(self, tmp_path):
        from simple_tensorflow_tpu.tools import graph_lint

        gpath, rpath = _missharded_graphdef(tmp_path)
        with pytest.raises(SystemExit):
            graph_lint.main([str(gpath), "--rules", str(rpath)])

    def test_subprocess_entry_point(self, tmp_path):
        """The literal acceptance-criterion invocation: python -m
        simple_tensorflow_tpu.tools.graph_lint --json --mesh 8 <gd>
        exits nonzero under --max-severity warning."""
        gpath, rpath = _missharded_graphdef(tmp_path)
        proc = subprocess.run(
            [sys.executable, "-m",
             "simple_tensorflow_tpu.tools.graph_lint", str(gpath),
             "--json", "--mesh", "8", "--rules", str(rpath),
             "--fetch", "loss", "--max-severity", "warning"],
            capture_output=True, text=True, timeout=300,
            cwd="/root/repo")
        assert proc.returncode == 1, proc.stderr
        codes = set()
        for line in proc.stdout.strip().splitlines():
            try:
                codes.add(json.loads(line).get("code"))
            except json.JSONDecodeError:
                pass
        assert "lint/replicated-large-tensor" in codes
        assert "lint/resharding-hotspot" in codes
