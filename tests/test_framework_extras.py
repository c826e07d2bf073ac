"""Tests: TensorArray, Defun, Example/parsing, misc ops, graph optimizer
passes, AOT compile, perf utils (SURVEY §2.1/§2.3/§2.10/§5)."""

import json

import numpy as np
import pytest

import simple_tensorflow_tpu as stf


@pytest.fixture(autouse=True)
def fresh_graph():
    stf.reset_default_graph()
    yield


class TestTensorArray:
    def test_write_read_stack(self):
        ta = stf.TensorArray(stf.float32, size=3, element_shape=[2])
        ta = ta.write(0, [1., 2.]).write(1, [3., 4.]).write(2, [5., 6.])
        with stf.Session() as sess:
            r, s = sess.run([ta.read(1), ta.stack()])
        assert r.tolist() == [3., 4.]
        assert s.tolist() == [[1., 2.], [3., 4.], [5., 6.]]

    def test_unstack_gather_concat(self):
        x = stf.constant(np.arange(12, dtype=np.float32).reshape(3, 2, 2))
        ta = stf.TensorArray(stf.float32, size=3,
                             element_shape=[2, 2]).unstack(x)
        with stf.Session() as sess:
            g = sess.run(ta.gather([2, 0]))
            c = sess.run(ta.concat())
        assert g.shape == (2, 2, 2) and g[0, 0, 0] == 8.0
        assert c.shape == (6, 2)

    def test_scatter_and_size(self):
        ta = stf.TensorArray(stf.int32, size=4, element_shape=[])
        ta = ta.scatter([1, 3], [10, 30])
        with stf.Session() as sess:
            assert sess.run(ta.stack()).tolist() == [0, 10, 0, 30]
            assert int(sess.run(ta.size())) == 4

    def test_gradient_through_tensor_array(self):
        x = stf.constant([1.0, 2.0])
        ta = stf.TensorArray(stf.float32, size=2, element_shape=[2])
        ta = ta.write(0, x * 2.0).write(1, x * 3.0)
        loss = stf.reduce_sum(ta.stack())
        (gx,) = stf.gradients(loss, [x])
        with stf.Session() as sess:
            assert sess.run(gx).tolist() == [5.0, 5.0]

    def test_dynamic_size_rejected(self):
        with pytest.raises(NotImplementedError):
            stf.TensorArray(stf.float32, size=2, element_shape=[1],
                            dynamic_size=True)


class TestDefun:
    def test_call_and_shape_specialization(self):
        calls = []

        @stf.Defun(stf.float32, stf.float32)
        def f(a, b):
            calls.append(1)
            return a * b + 1.0

        y1 = f(stf.constant([1., 2.]), stf.constant([3., 4.]))
        y2 = f(stf.constant([5., 6.]), stf.constant([7., 8.]))  # cache hit
        y3 = f(stf.constant(2.0), stf.constant(3.0))  # new signature
        with stf.Session() as sess:
            assert sess.run(y1).tolist() == [4., 9.]
            assert sess.run(y2).tolist() == [36., 49.]
            assert float(sess.run(y3)) == 7.0
        assert len(calls) == 2  # traced once per shape signature

    def test_capture_and_gradient(self):
        c = stf.constant(3.0)

        @stf.Defun(stf.float32)
        def g(x):
            return x * x * c  # captures c

        x = stf.constant(2.0)
        y = g(x)
        (dx,) = stf.gradients(y, [x])
        with stf.Session() as sess:
            assert float(sess.run(y)) == 12.0
            assert float(sess.run(dx)) == 12.0  # 2*x*c

    def test_multi_output(self):
        @stf.Defun(stf.float32)
        def h(x):
            return x + 1.0, x * 2.0

        a, b = h(stf.constant(4.0))
        with stf.Session() as sess:
            assert sess.run([a, b]) == [5.0, 8.0]


class TestExampleProto:
    def test_roundtrip(self):
        ex = stf.train.Example(features=stf.train.Features(feature={
            "label": stf.train.int64_feature(5),
            "w": stf.train.float_feature([0.5, 2.5]),
            "s": stf.train.bytes_feature([b"ab", b""]),
        }))
        data = ex.SerializeToString()
        back = stf.train.Example.FromString(data)
        assert back.features.feature["label"].int64_list.value == [5]
        assert back.features.feature["w"].float_list.value == [0.5, 2.5]
        assert back.features.feature["s"].bytes_list.value == [b"ab", b""]

    def test_negative_int64(self):
        ex = stf.train.make_example(v=[-3, 7])
        back = stf.train.Example.FromString(ex.SerializeToString())
        assert back.features.feature["v"].int64_list.value == [-3, 7]

    def test_parse_example_graph(self):
        exs = [stf.train.make_example(label=i, w=[float(i), 1.0],
                                      tags=list(range(i)))
               for i in range(3)]
        sers = np.array([e.SerializeToString() for e in exs], dtype=object)
        s = stf.placeholder(stf.string, [3])
        feats = stf.parse_example(s, {
            "label": stf.FixedLenFeature([], stf.int64),
            "w": stf.FixedLenFeature([2], stf.float32),
            "tags": stf.VarLenFeature(stf.int64),
        })
        with stf.Session() as sess:
            out = sess.run(feats, {s: sers})
        assert out["label"].tolist() == [0, 1, 2]
        assert out["w"][2].tolist() == [2.0, 1.0]
        assert out["tags"].values.tolist() == [0, 0, 1]
        assert out["tags"].dense_shape.tolist() == [3, 2]

    def test_parse_single_example(self):
        data = stf.train.make_example(x=[1.5]).SerializeToString()
        feats = stf.parse_single_example(
            stf.constant(np.asarray(data, dtype=object)),
            {"x": stf.FixedLenFeature([1], stf.float32)})
        with stf.Session() as sess:
            assert sess.run(feats["x"]).tolist() == [1.5]

    def test_fixed_len_default(self):
        data = stf.train.make_example(a=1).SerializeToString()
        s = stf.placeholder(stf.string, [1])
        feats = stf.parse_example(s, {
            "missing": stf.FixedLenFeature([], stf.int64, default_value=9)})
        with stf.Session() as sess:
            out = sess.run(feats, {s: np.array([data], dtype=object)})
        assert out["missing"].tolist() == [9]

    def test_decode_raw(self):
        s = stf.placeholder(stf.string, [2])
        d = stf.decode_raw(s, stf.int16)
        with stf.Session() as sess:
            out = sess.run(d, {s: np.array(
                [np.int16([1, 2]).tobytes(), np.int16([3, 4]).tobytes()],
                dtype=object)})
        assert out.tolist() == [[1, 2], [3, 4]]


class TestMiscOps:
    def test_confusion_matrix(self):
        cm = stf.confusion_matrix(stf.constant([1, 2, 4]),
                                  stf.constant([2, 2, 4]), num_classes=5)
        with stf.Session() as sess:
            m = sess.run(cm)
        assert m[1, 2] == 1 and m[2, 2] == 1 and m[4, 4] == 1
        assert m.sum() == 3

    def test_confusion_matrix_weights(self):
        cm = stf.confusion_matrix(stf.constant([0, 1]), stf.constant([0, 1]),
                                  num_classes=2,
                                  weights=stf.constant([0.5, 2.0]))
        with stf.Session() as sess:
            m = sess.run(cm)
        assert m[0, 0] == 0.5 and m[1, 1] == 2.0

    def test_histogram(self):
        h = stf.histogram_fixed_width(
            stf.constant([-1.0, 0.1, 0.49, 0.5, 2.0]), [0.0, 1.0], nbins=2)
        with stf.Session() as sess:
            # out-of-range clamps into edge bins (ref histogram_ops)
            assert sess.run(h).tolist() == [3, 2]

    def test_bitcast(self):
        b = stf.bitcast(stf.constant([1.0], stf.float32), stf.uint32)
        with stf.Session() as sess:
            assert sess.run(b).tolist() == [0x3F800000]

    def test_sets(self):
        pad = np.iinfo(np.int32).min
        a = stf.constant([[1, 2, 3], [4, 5, 6]])
        b = stf.constant([[2, 3, 9], [7, 8, 9]])
        with stf.Session() as sess:
            inter = sess.run(stf.sets.intersection(a, b))
            diff = sess.run(stf.sets.difference(a, b))
            union = sess.run(stf.sets.union(a, b))
            size = sess.run(stf.sets.size(a))
        assert sorted(v for v in inter[0] if v != pad) == [2, 3]
        assert [v for v in inter[1] if v != pad] == []
        assert sorted(v for v in diff[0] if v != pad) == [1]
        assert sorted(v for v in union[1] if v != pad) == [4, 5, 6, 7, 8, 9]
        assert size.tolist() == [3, 3]

    def test_lbeta(self):
        # Beta(1,1) = 1 -> log 0 ; Beta(2,2) = 1/6
        lb = stf.lbeta(stf.constant([[1.0, 1.0], [2.0, 2.0]]))
        with stf.Session() as sess:
            v = sess.run(lb)
        np.testing.assert_allclose(v, [0.0, np.log(1 / 6)], atol=1e-5)

    def test_verify_tensor_all_finite(self):
        x = stf.placeholder(stf.float32, [2])
        y = stf.verify_tensor_all_finite(x, "bad x") * 2.0
        with stf.Session() as sess:
            assert sess.run(y, {x: np.ones(2, np.float32)}).tolist() == [2., 2.]
            with pytest.raises(stf.errors.InvalidArgumentError):
                sess.run(y, {x: np.array([1.0, np.nan], np.float32)})


class TestGraphOptimizer:
    def _graphdef(self):
        a = stf.constant(2.0, name="a")
        b = stf.constant(3.0, name="b")
        c = stf.add(a, b, name="c")  # foldable
        x = stf.placeholder(stf.float32, [], name="x")
        y1 = stf.multiply(x, c, name="y1")
        y2 = stf.multiply(x, c, name="y2")  # CSE twin of y1
        dead = stf.square(x, name="dead")
        out = stf.add(y1, y2, name="out")
        from simple_tensorflow_tpu.framework import graph_io

        return graph_io.graph_to_graphdef(stf.get_default_graph()), out

    def test_constant_folding(self):
        gd, _ = self._graphdef()
        folded = stf.graph_optimizer.constant_folding(gd)
        c = [n for n in folded["node"] if n["name"] == "c"][0]
        assert c["op"] == "Const"

    def test_cse(self):
        gd, _ = self._graphdef()
        opt = stf.graph_optimizer.common_subexpression_elimination(gd)
        names = [n["name"] for n in opt["node"]]
        assert ("y1" in names) != ("y2" in names)  # one of the twins merged
        out = [n for n in opt["node"] if n["name"] == "out"][0]
        assert out["input"][0] == out["input"][1]

    def test_dce(self):
        gd, _ = self._graphdef()
        pruned = stf.graph_optimizer.dead_code_elimination(gd, ["out"])
        names = [n["name"] for n in pruned["node"]]
        assert "dead" not in names and "out" in names

    def test_full_pipeline_preserves_semantics(self):
        gd, out = self._graphdef()
        opt = stf.graph_optimizer.optimize(gd, keep=["out"])
        # import the optimized graph and run both
        with stf.Session() as sess:
            ref = sess.run(out, {"x:0": np.float32(4.0)})
        g2 = stf.Graph()
        with g2.as_default():
            from simple_tensorflow_tpu.framework import graph_io

            graph_io.import_graph_def(opt, name="")
            with stf.Session() as sess:
                got = sess.run("out:0", {"x:0": np.float32(4.0)})
        assert float(ref) == float(got) == 40.0


class TestAot:
    def test_compile_and_run(self):
        from simple_tensorflow_tpu.compiler import aot

        x = stf.placeholder(stf.float32, [4], name="x")
        y = stf.reduce_sum(x * x)
        exe = aot.compile_fetches(y, [x])
        (out,) = exe(np.ones(4, np.float32) * 2.0)
        assert float(out) == 16.0
        assert "HloModule" in exe.hlo_text or "module" in exe.hlo_text
        assert exe.cache_key

    def test_stateful_rejected(self):
        from simple_tensorflow_tpu.compiler import aot

        v = stf.Variable(stf.ones([2]), name="v")
        with pytest.raises(ValueError):
            aot.compile_fetches(v.value() * 2.0, [])

    def test_dynamic_shape_rejected(self):
        from simple_tensorflow_tpu.compiler import aot

        x = stf.placeholder(stf.float32, [None, 2], name="x")
        with pytest.raises(ValueError):
            aot.compile_fetches(stf.reduce_sum(x), [x])


class TestPerf:
    def test_mfu_and_roofline(self):
        from simple_tensorflow_tpu.utils import perf

        class V5e:
            platform, device_kind = "tpu", "TPU v5 lite"

        assert perf.mfu(98.5e12, 1.0, device=V5e) == pytest.approx(0.5)
        r = perf.roofline(step_flops=1e12, step_bytes=1e9, device=V5e)
        assert r["compute_bound"] == (r["intensity_flops_per_byte"]
                                      >= r["ridge_point"])
        assert r["ridge_point"] == pytest.approx(197e12 / 819e9)

    def test_no_utilization_from_nominal_or_unknown_peaks(self):
        """The CPU's figures are nominal planning inputs: planning maths
        may read them, a utilization may not; an accelerator that is not
        in the table is an error everywhere."""
        from simple_tensorflow_tpu.utils import perf

        assert perf.chip_spec() == perf._CPU_NOMINAL[:2]
        assert perf.chip_hbm_bytes() == perf._CPU_NOMINAL[2]
        with pytest.raises(ValueError, match="no published peak"):
            perf.mfu(1e12, 1.0)
        with pytest.raises(ValueError, match="no published peak"):
            perf.roofline(1e12, 1e9)

        class Unknown:
            platform, device_kind = "tpu", "TPU v99"

        for fn in (perf.chip_spec, perf.chip_hbm_bytes,
                   perf.published_chip):
            with pytest.raises(ValueError, match="TPU v99"):
                fn(Unknown)

    def test_step_timer(self):
        from simple_tensorflow_tpu.utils import perf

        t = perf.StepTimer()
        t.start()
        for _ in range(3):
            t.mark()
        s = t.summary()
        assert s["mean_s"] >= 0 and t.steps == 3

    def test_perf_report_with_compiled(self):
        import jax

        from simple_tensorflow_tpu.utils import perf

        f = jax.jit(lambda a, b: a @ b)
        x = np.ones((64, 64), np.float32)
        compiled = f.lower(x, x).compile()
        rep = perf.PerfReport(compiled)
        rep.timer.start()
        f(x, x)
        rep.step_done()
        out = rep.report()
        assert out.get("achieved_tflops", 0) >= 0
        # the CPU has no published peak: no utilization in the report
        assert "mfu" not in out and "roofline_fraction_of_peak" not in out


class TestConfigProtoTransferGuard:
    """ConfigProto (ref config.proto) + L0 transfer guards (SURVEY §1)."""

    def test_config_proto_fields(self):
        c = stf.ConfigProto(allow_soft_placement=True,
                            log_device_placement=True,
                            gpu_options=stf.GPUOptions(allow_growth=True))
        assert c.allow_soft_placement and c.log_device_placement
        assert c.gpu_options.allow_growth
        with pytest.raises(ValueError):
            stf.ConfigProto(transfer_guard="never")

    def test_disallow_raises_on_hot_path_feed(self):
        stf.reset_default_graph()
        cfg = stf.ConfigProto(transfer_guard="disallow",
                              transfer_guard_threshold_bytes=1024)
        x = stf.placeholder(stf.float32, [64, 64], name="gx")
        y = stf.reduce_sum(x)
        sess = stf.Session(config=cfg)
        feed = {x: np.ones((64, 64), np.float32)}  # 16 KiB > threshold
        # first two runs are warmup/compile: allowed
        sess.run(y, feed)
        sess.run(y, feed)
        with pytest.raises(stf.errors.InvalidArgumentError,
                           match="prefetch_to_device"):
            sess.run(y, feed)

    def test_small_feeds_and_allow_mode_pass(self):
        stf.reset_default_graph()
        cfg = stf.ConfigProto(transfer_guard="disallow",
                              transfer_guard_threshold_bytes=1 << 20)
        x = stf.placeholder(stf.float32, [4], name="sx")
        y = stf.reduce_sum(x)
        sess = stf.Session(config=cfg)
        for _ in range(5):
            sess.run(y, {x: np.ones(4, np.float32)})  # tiny: fine
        stf.reset_default_graph()
        x2 = stf.placeholder(stf.float32, [64, 64], name="ax")
        y2 = stf.reduce_sum(x2)
        s2 = stf.Session()  # no config: guard off
        for _ in range(5):
            s2.run(y2, {x2: np.ones((64, 64), np.float32)})

    def test_disallow_raises_on_big_fetch(self):
        stf.reset_default_graph()
        cfg = stf.ConfigProto(transfer_guard="disallow",
                              transfer_guard_threshold_bytes=1024)
        x = stf.placeholder(stf.float32, [4], name="fx")
        big = stf.tile(stf.reshape(x, [1, 4]), [512, 1])  # 8 KiB out
        sess = stf.Session(config=cfg)
        feed = {x: np.ones(4, np.float32)}
        sess.run(big, feed)
        sess.run(big, feed)
        with pytest.raises(stf.errors.InvalidArgumentError,
                           match="keep large results on device"):
            sess.run(big, feed)


class TestMakeCallable:
    """make_callable fast path (ref session.py make_callable): resolved
    once, per-call dispatch goes straight to the cached XLA step."""

    def test_training_loop_matches_run(self):
        stf.reset_default_graph()
        rng = np.random.RandomState(0)
        X = rng.rand(32, 4).astype(np.float32)
        Y = (X @ np.float32([[1], [2], [-1], [0.5]])).ravel()
        x = stf.placeholder(stf.float32, [32, 4], name="cx")
        y = stf.placeholder(stf.float32, [32], name="cy")
        w = stf.Variable(np.zeros((4,), np.float32), name="cw")
        pred = stf.reduce_sum(x * w, axis=1)
        loss = stf.reduce_mean(stf.square(pred - y))
        opt = stf.train.GradientDescentOptimizer(0.1)
        train = opt.minimize(loss)
        sess = stf.Session()
        sess.run(stf.global_variables_initializer())
        step_fn = sess.make_callable([train, loss], feed_list=[x, y])
        losses = [step_fn(X, Y)[1] for _ in range(20)]
        assert losses[-1] < losses[0] * 0.5
        # the state the fast path advanced is the state run() sees: the
        # loss run() computes now equals the pre-update loss of the NEXT
        # fast-path step
        final = sess.run(loss, {x: X, y: Y})
        next_loss = step_fn(X, Y)[1]
        np.testing.assert_allclose(final, next_loss, rtol=1e-5)

    def test_fetch_structures_and_arity_check(self):
        stf.reset_default_graph()
        a = stf.placeholder(stf.float32, [2], name="fa")
        b = stf.square(a)
        sess = stf.Session()
        f = sess.make_callable({"sq": b, "in": a}, feed_list=[a])
        out1 = f(np.float32([2, 3]))
        out2 = f(np.float32([4, 5]))  # second call = fast path
        np.testing.assert_allclose(out1["sq"], [4, 9])
        np.testing.assert_allclose(out2["sq"], [16, 25])
        np.testing.assert_allclose(out2["in"], [4, 5])
        with pytest.raises(ValueError, match="Expected 1 feed"):
            f()

    def test_host_stage_fetches_stay_on_general_path(self):
        # string const fetch involves host handling: must still work
        stf.reset_default_graph()
        a = stf.placeholder(stf.float32, [2], name="ha")
        s = stf.constant(np.asarray(["x", "y"], object))
        sess = stf.Session()
        f = sess.make_callable([stf.square(a), s], feed_list=[a])
        for _ in range(3):
            sq, sv = f(np.float32([1, 2]))
            np.testing.assert_allclose(sq, [1, 4])

    def test_fast_path_validates_shape_and_closed_session(self):
        stf.reset_default_graph()
        x = stf.placeholder(stf.float32, [4], name="vx")
        y = stf.square(x)
        sess = stf.Session()
        f = sess.make_callable(y, feed_list=[x])
        f(np.ones(4, np.float32))
        f(np.ones(4, np.float32))  # adopted
        with pytest.raises(ValueError, match="Cannot feed value of shape"):
            f(np.ones((4, 1), np.float32))
        sess.close()
        with pytest.raises(RuntimeError, match="closed Session"):
            f(np.ones(4, np.float32))

    def test_fast_path_honors_transfer_guard(self):
        stf.reset_default_graph()
        cfg = stf.ConfigProto(transfer_guard="disallow",
                              transfer_guard_threshold_bytes=1024)
        x = stf.placeholder(stf.float32, [64, 64], name="tx")
        y = stf.reduce_sum(x)
        sess = stf.Session(config=cfg)
        f = sess.make_callable(y, feed_list=[x])
        big = np.ones((64, 64), np.float32)
        f(big)  # slow-path warmups (n_calls 1..2 allowed)
        with pytest.raises(stf.errors.InvalidArgumentError,
                           match="prefetch_to_device"):
            for _ in range(3):
                f(big)


class TestRecomputeGrad:
    def test_values_and_grads_match_plain(self):
        stf.reset_default_graph()
        x = stf.placeholder(stf.float32, [8, 16], name="rgx")
        w = stf.Variable(np.random.RandomState(0).randn(16, 16)
                         .astype(np.float32), name="rgw")

        def block(h):
            return stf.tanh(stf.matmul(h, w)) + h

        y_plain = block(block(x))
        blk = stf.recompute_grad(block)
        y_rc = blk(blk(x))
        (gp,) = stf.gradients(stf.reduce_sum(stf.square(y_plain)), [w])
        (gr,) = stf.gradients(stf.reduce_sum(stf.square(y_rc)), [w])
        sess = stf.Session()
        sess.run(stf.global_variables_initializer())
        xv = np.random.RandomState(1).randn(8, 16).astype(np.float32)
        out = sess.run({"p": y_plain, "r": y_rc, "gp": gp, "gr": gr},
                       {x: xv})
        np.testing.assert_allclose(out["p"], out["r"], rtol=1e-6)
        np.testing.assert_allclose(out["gp"], out["gr"], rtol=1e-6)

    def test_backward_rematerializes(self):
        # structural: under jax.checkpoint the body's tanh is REPLAYED in
        # the backward, so the lowered program contains more tanh ops for
        # the recompute variant than for the plain one

        from simple_tensorflow_tpu.framework import lowering as lowering_mod

        def count_tanh(use_recompute):
            stf.reset_default_graph()
            x = stf.placeholder(stf.float32, [4, 8], name="ctx")
            w = stf.Variable(np.eye(8, dtype=np.float32), name="ctw")

            def block(h):
                return stf.tanh(stf.matmul(h, w))

            f = stf.recompute_grad(block) if use_recompute else block
            y = f(f(x))
            (g,) = stf.gradients(stf.reduce_sum(y), [w])
            sess = stf.Session()
            sess.run(stf.global_variables_initializer())
            xv = np.zeros((4, 8), np.float32)
            _ = sess.run(g, {x: xv})  # compile
            step = max((v for v in sess._cache.values()
                        if v.has_device_stage),
                       key=lambda s: len(s.device_ops))
            feeds = sess._normalize_feeds({x: xv})
            fa = {t.name: feeds[t] for t in step.feed_tensors}
            state = dict(sess._variable_store.values)
            txt = step.jitted.lower(state, fa, sess._base_key,
                                    np.uint32(1)).as_text()
            return txt.count("stablehlo.tanh")

        assert count_tanh(True) > count_tanh(False)

    def test_per_layer_lambdas_get_distinct_bodies(self):
        # regression: the trace cache was keyed by id(func); a discarded
        # lambda's recycled id aliased another layer's traced body, so
        # layers silently shared (and trained) the wrong weights
        stf.reset_default_graph()
        x = stf.placeholder(stf.float32, [4, 8], name="dlx")
        ws = [stf.Variable(np.random.RandomState(i).randn(8, 8)
                           .astype(np.float32) * 0.3, name=f"dlw{i}")
              for i in range(4)]
        h = x
        for i in range(4):
            h = stf.recompute_grad(
                lambda hh, w=ws[i]: stf.tanh(stf.matmul(hh, w)))(h)
        g = stf.get_default_graph()
        calls = [op for op in g.get_operations()
                 if op.type == "RecomputeGradCall"]
        caps = [sorted(t.name for t in op.inputs[1:]) for op in calls]
        assert caps == [["dlw0:0"], ["dlw1:0"], ["dlw2:0"], ["dlw3:0"]], caps
        grads = stf.gradients(stf.reduce_sum(stf.square(h)), ws)
        sess = stf.Session()
        sess.run(stf.global_variables_initializer())
        gv = sess.run(list(grads),
                      {x: np.random.RandomState(9).randn(4, 8)
                       .astype(np.float32)})
        for a in gv:
            assert float(np.abs(np.asarray(a)).sum()) > 0.0
