"""Session.run_steps: device-resident multi-step loops (ISSUE 4).

Equivalence contract: run_steps(n) must be bit-exact with n sequential
Session.run calls — same variable trajectories, same global_step, same
stateful-RNG streams (the fused loop derives per-step keys from the
SAME run counters the sequential path would use), same learning-rate
schedules. Loop-unsafe plans (host-effectful ops, host sinks,
iterators) must refuse fusion with a structured diagnostic naming the
blocking op, fall back to sequential runs, and count the reason on
/stf/session/loop_fusion_fallbacks.
"""

import numpy as np
import pytest

import simple_tensorflow_tpu as stf
from simple_tensorflow_tpu import analysis
from simple_tensorflow_tpu import data as stf_data
from simple_tensorflow_tpu.platform import monitoring


@pytest.fixture(autouse=True)
def fresh_graph():
    stf.reset_default_graph()
    yield


def _counter_cells(name):
    return monitoring.export().get(name, {}).get("cells", {})


def _fused_steps_count():
    return _counter_cells("/stf/session/fused_steps_amortized").get("", 0)


def _two_sessions(graph):
    """Two fresh sessions over the same graph, identically initialized
    (one init run each, so their RNG counters stay aligned)."""
    sa = stf.Session(graph=graph)
    sb = stf.Session(graph=graph)
    sa.run(stf.global_variables_initializer())
    sb.run(stf.global_variables_initializer())
    return sa, sb


class TestEquivalence:
    def test_mnist_convnet_fused_matches_sequential(self):
        """Convnet with dropout (stateful RNG), Adam slots, and
        global_step: n fused steps == n sequential runs — integers bit
        for bit, floats to what one XLA program can promise of
        another (see the tolerances below)."""
        from simple_tensorflow_tpu.models import mnist

        stf.set_random_seed(11)
        m = mnist.convnet_model(batch_size=4)
        rng = np.random.RandomState(0)
        feed = {m["x"]: rng.rand(4, 28, 28, 1).astype(np.float32),
                m["y_"]: rng.randint(0, 10, 4).astype(np.int32),
                m["keep_prob"]: 0.7}
        g = stf.get_default_graph()
        sa, sb = _two_sessions(g)
        gs = stf.train.get_global_step(g)

        n = 5
        seq = [sa.run([m["train_op"], m["loss"], gs._ref], feed)[1:]
               for _ in range(n)]
        fused0 = _fused_steps_count()
        out = sb.run_steps([m["train_op"], m["loss"], gs._ref], n=n,
                           feed_dict=feed, output_mode="stacked")
        assert _fused_steps_count() == fused0 + n  # really went fused
        assert out[0] is None  # fetched Operation
        seq_losses = np.array([l for l, _ in seq])
        # float fetches: same ops, same RNG streams, same dtype — XLA
        # may reassociate inside the scan body, so equality is to the
        # last ULP, not the last bit (measured max diff ~1e-7 relative)
        np.testing.assert_allclose(out[1], seq_losses, rtol=3e-6, atol=0)
        # integer state (global_step) must be EXACT
        np.testing.assert_array_equal(
            out[2], np.array([s for _, s in seq]))
        # terminal variable state identical (weights + Adam slots)
        for name in sa._variable_store.values:
            a = np.asarray(sa._variable_store.values[name])
            b = np.asarray(sb._variable_store.values[name])
            if np.issubdtype(a.dtype, np.integer):
                np.testing.assert_array_equal(a, b,
                                              err_msg=f"{name} diverged")
            else:
                # accumulated over n Adam steps. The scan body and the
                # single step are different XLA programs, so gradients
                # differ in the last ULP; Adam divides by sqrt(v), which
                # turns a last-ULP difference in a near-zero gradient
                # into a visible fraction of one step (lr = 1e-3). On
                # JAX 0.9 / this XLA the worst of 3.2 M elements is
                # 8.8e-6 after 5 steps; the bound is 2 % of one step —
                # 250x below the 5 * lr a real divergence would show.
                np.testing.assert_allclose(
                    a, b, rtol=1e-4, atol=2e-5,
                    err_msg=f"variable {name} diverged")

    def test_lr_schedule_and_global_step(self):
        """exponential_decay(global_step) must see the advancing step
        INSIDE the fused window."""
        stf.set_random_seed(5)
        gs = stf.train.get_or_create_global_step()
        x = stf.placeholder(stf.float32, [4, 8], name="x")
        w = stf.Variable(stf.ones([8, 1]), name="w")
        loss = stf.reduce_mean(stf.square(stf.matmul(x, w)))
        lr = stf.train.exponential_decay(0.1, gs, decay_steps=2,
                                         decay_rate=0.5, staircase=True)
        train = stf.train.GradientDescentOptimizer(lr).minimize(
            loss, global_step=gs)
        g = stf.get_default_graph()
        sa, sb = _two_sessions(g)
        rng = np.random.RandomState(1)
        batches = [rng.rand(4, 8).astype(np.float32) for _ in range(6)]

        seq = [sa.run([train, loss, gs._ref], {x: b})[1:] for b in batches]
        out = sb.run_steps([train, loss, gs._ref], n=6,
                           feed_iterator=({x: b} for b in batches),
                           output_mode="stacked")
        np.testing.assert_allclose(out[1], np.array([l for l, _ in seq]),
                                   rtol=3e-6, atol=0)
        np.testing.assert_array_equal(out[2],
                                      np.array([s for _, s in seq]))
        np.testing.assert_allclose(np.asarray(sa.run(w._ref)),
                                   np.asarray(sb.run(w._ref)),
                                   rtol=3e-6, atol=1e-7)

    def test_scan_bearing_model(self):
        """A model with a lax.scan in its step (FuncGraph body) fuses
        into the outer step loop — scan-in-scan."""
        x = stf.placeholder(stf.float32, [3, 4], name="x")
        w = stf.Variable(stf.ones([4]), name="w")

        def body(carry, row):
            return stf.tanh(carry + row * w._ref)

        scanned = stf.scan(body, x, initializer=stf.zeros([4]))
        loss = stf.reduce_mean(stf.square(scanned[-1]))
        train = stf.train.GradientDescentOptimizer(0.1).minimize(loss)
        g = stf.get_default_graph()
        sa, sb = _two_sessions(g)
        rng = np.random.RandomState(2)
        feed = {x: rng.rand(3, 4).astype(np.float32)}
        seq = [sa.run([train, loss], feed)[1] for _ in range(4)]
        out = sb.run_steps([train, loss], n=4, feed_dict=feed,
                           output_mode="stacked")
        np.testing.assert_array_equal(out[1], np.array(seq))

    def test_last_vs_stacked_output_modes(self):
        x = stf.placeholder(stf.float32, [2], name="x")
        v = stf.Variable(stf.zeros([2]), name="v")
        acc = stf.assign_add(v, x)
        sess = stf.Session()
        sess.run(stf.global_variables_initializer())
        ones = np.ones(2, np.float32)
        stacked = sess.run_steps(acc, n=3, feed_dict={x: ones},
                                 output_mode="stacked")
        assert stacked.shape == (3, 2)
        np.testing.assert_array_equal(stacked[:, 0], [1.0, 2.0, 3.0])
        last = sess.run_steps(acc, n=2, feed_dict={x: ones},
                              output_mode="last")
        np.testing.assert_array_equal(last, [5.0, 5.0])

    def test_stacked_feeds_superbatch(self):
        x = stf.placeholder(stf.float32, [2], name="x")
        v = stf.Variable(stf.zeros([2]), name="v")
        acc = stf.assign_add(v, x)
        sess = stf.Session()
        sess.run(stf.global_variables_initializer())
        sb = np.arange(8, dtype=np.float32).reshape(4, 2)
        out = sess.run_steps(acc, n=4, stacked_feeds={x: sb},
                             output_mode="last")
        np.testing.assert_array_equal(out, sb.sum(axis=0))

    def test_stacked_feeds_wrong_lead_dim_raises(self):
        x = stf.placeholder(stf.float32, [2], name="x")
        y = stf.identity(x)
        sess = stf.Session()
        with pytest.raises(ValueError, match="leading dim"):
            sess.run_steps(y, n=4,
                           stacked_feeds={x: np.zeros((3, 2), np.float32)})

    def test_feed_iterator_exhausted_raises(self):
        from simple_tensorflow_tpu.framework import errors

        x = stf.placeholder(stf.float32, [2], name="x")
        v = stf.Variable(stf.zeros([2]), name="v")
        acc = stf.assign_add(v, x)
        sess = stf.Session()
        sess.run(stf.global_variables_initializer())
        feeds = [{x: np.ones(2, np.float32)}] * 2
        with pytest.raises(errors.OutOfRangeError,
                           match="exhausted after 2 of 3"):
            sess.run_steps(acc, n=3, feed_iterator=iter(feeds))


class TestFallback:
    def test_print_refuses_fusion_with_diagnostic(self):
        """A device op with a declared io effect (Print) must refuse
        fusion, name the op, count the reason, and still produce the
        correct values via the sequential fallback."""
        from simple_tensorflow_tpu.ops import logging_ops

        x = stf.placeholder(stf.float32, [2], name="x")
        y = logging_ops.Print(x * 2.0, [x], message="v=", name="my_print")
        sess = stf.Session()
        before = dict(_counter_cells("/stf/session/loop_fusion_fallbacks"))
        out = sess.run_steps(y, n=3, feed_dict={x: np.ones(2, np.float32)},
                             output_mode="stacked")
        np.testing.assert_array_equal(out, np.full((3, 2), 2.0))
        after = _counter_cells("/stf/session/loop_fusion_fallbacks")
        assert after.get("host_effectful_op", 0) == \
            before.get("host_effectful_op", 0) + 1
        # the structured diagnostic names the blocking op
        step = next(iter(sess._cache.values()))
        static_diags = step.fusion_diags[0]
        assert any(d.code == "loop_fusion/host_effectful_op"
                   and d.op_name == "my_print" for d in static_diags), \
            [d.format() for d in static_diags]

    def test_summary_host_sink_defers_under_last_mode(self):
        """Pure host sinks (summary serialization only OBSERVES device
        values) no longer split the window: under output_mode="last"
        the sink defers to once-per-window on last-step values, so the
        n steps fuse with no host_sink_op fallback."""
        x = stf.placeholder(stf.float32, [2], name="x")
        s = stf.summary.scalar("mean_x", stf.reduce_mean(x * 3.0))
        sess = stf.Session()
        before = dict(_counter_cells("/stf/session/loop_fusion_fallbacks"))
        fused0 = _fused_steps_count()
        out = sess.run_steps(s, n=2, feed_dict={x: np.ones(2, np.float32)})
        assert out is not None  # serialized summary from the last step
        after = _counter_cells("/stf/session/loop_fusion_fallbacks")
        assert after.get("host_sink_op", 0) == \
            before.get("host_sink_op", 0)
        assert _fused_steps_count() == fused0 + 2

    def test_summary_host_sink_refuses_fusion_when_stacked(self):
        """output_mode="stacked" needs the summary serialized PER STEP,
        which the deferred once-per-window stage cannot provide — still
        a host_sink_op fallback."""
        x = stf.placeholder(stf.float32, [2], name="x")
        s = stf.summary.scalar("mean_x", stf.reduce_mean(x * 3.0))
        sess = stf.Session()
        before = dict(_counter_cells("/stf/session/loop_fusion_fallbacks"))
        fused0 = _fused_steps_count()
        out = sess.run_steps(s, n=2, feed_dict={x: np.ones(2, np.float32)},
                             output_mode="stacked")
        assert len(out) == 2  # one serialized summary per step
        after = _counter_cells("/stf/session/loop_fusion_fallbacks")
        assert after.get("host_sink_op", 0) == \
            before.get("host_sink_op", 0) + 1
        assert _fused_steps_count() == fused0  # nothing fused

    def test_iterator_feed_refuses_fusion(self):
        """IteratorGetNext is a host-stage op: per-step Python pulls
        cannot live inside the device loop."""
        ds = stf_data.Dataset.from_tensor_slices(
            np.arange(12, dtype=np.float32)).batch(2)
        it = ds.make_one_shot_iterator()
        nxt = it.get_next()
        total = stf.reduce_sum(nxt)
        sess = stf.Session()
        before = dict(_counter_cells("/stf/session/loop_fusion_fallbacks"))
        out = sess.run_steps(total, n=3, output_mode="stacked")
        np.testing.assert_array_equal(out, [1.0, 5.0, 9.0])
        after = _counter_cells("/stf/session/loop_fusion_fallbacks")
        assert after.get("host_stage_op", 0) == \
            before.get("host_stage_op", 0) + 1

    def test_uninitialized_variables_fall_back(self):
        """Assign to a variable with no device value yet: the carry has
        no initial entry, so the window must run unfused (where the
        init-before-read contract applies per step)."""
        v = stf.Variable(stf.zeros([2]), name="v")
        init = stf.global_variables_initializer()
        sess = stf.Session()
        before = dict(_counter_cells("/stf/session/loop_fusion_fallbacks"))
        sess.run_steps(init, n=2)
        after = _counter_cells("/stf/session/loop_fusion_fallbacks")
        assert after.get("uninitialized_write", 0) == \
            before.get("uninitialized_write", 0) + 1
        np.testing.assert_array_equal(sess.run(v._ref), np.zeros(2))

    def test_checknumerics_fuses_and_raises_post_commit(self):
        """The numeric_check_op fusion blocker is retired: checks ride
        the fused window's per-step ys. A clean window fuses (no
        fallback counted); a poisoned step raises AFTER the window
        commits, naming the failing window step."""
        x = stf.placeholder(stf.float32, [2], name="x")
        y = stf.check_numerics(x * 2.0, "bad x")
        sess = stf.Session()
        before = dict(_counter_cells("/stf/session/loop_fusion_fallbacks"))
        out = sess.run_steps(y, n=2, feed_dict={x: np.ones(2, np.float32)})
        np.testing.assert_array_equal(out, np.full(2, 2.0))
        after = _counter_cells("/stf/session/loop_fusion_fallbacks")
        assert after == before  # fused: no fallback reason counted
        bad = np.array([1.0, np.nan], np.float32)
        with pytest.raises(stf.errors.InvalidArgumentError) as ei:
            sess.run_steps(y, n=3, stacked_feeds={
                x: np.stack([np.ones(2, np.float32), bad,
                             np.ones(2, np.float32)])})
        assert "bad x" in str(ei.value)
        assert "step 1 of 3" in str(ei.value)


class TestDataWiring:
    def test_superbatch_stacks_batches(self):
        ds = (stf_data.Dataset.from_tensor_slices(
            np.arange(16, dtype=np.int32)).batch(2).superbatch(4))
        sb = next(iter(ds))
        assert sb.shape == (4, 2)
        np.testing.assert_array_equal(sb[0], [0, 1])
        np.testing.assert_array_equal(sb[3], [6, 7])

    def test_prefetch_to_device_superbatch_feeds_run_steps(self):
        import jax

        ds = (stf_data.Dataset.from_tensor_slices(
            np.arange(24, dtype=np.float32)).batch(2)
            .prefetch_to_device(superbatch=3))
        it = iter(ds)
        sb = next(it)
        assert isinstance(sb, jax.Array) and sb.shape == (3, 2)
        x = stf.placeholder(stf.float32, [2], name="x")
        v = stf.Variable(stf.zeros([]), name="v")
        acc = stf.assign_add(v, stf.reduce_sum(x))
        sess = stf.Session()
        sess.run(stf.global_variables_initializer())
        out = sess.run_steps(acc, n=3, stacked_feeds={x: sb},
                             output_mode="last")
        assert float(out) == float(np.arange(6).sum())

    def test_superbatch_dict_structure(self):
        ds = (stf_data.Dataset.from_tensor_slices(
            {"a": np.arange(8), "b": np.arange(8) * 2})
            .batch(2).superbatch(2))
        sb = next(iter(ds))
        assert set(sb) == {"a", "b"}
        assert sb["a"].shape == (2, 2)


class TestMonitoredDriving:
    def _model(self):
        gs = stf.train.get_or_create_global_step()
        x = stf.placeholder(stf.float32, [4, 8], name="x")
        w = stf.Variable(stf.ones([8, 1]), name="w")
        loss = stf.reduce_mean(stf.square(stf.matmul(x, w)))
        train = stf.train.GradientDescentOptimizer(0.05).minimize(
            loss, global_step=gs)
        feed = {x: np.random.RandomState(0).rand(4, 8).astype(np.float32)}
        return train, loss, feed

    def test_transparent_fusion_with_stop_and_counter_hooks(self):
        train, loss, feed = self._model()
        hooks = [stf.train.StopAtStepHook(last_step=25),
                 stf.train.StepCounterHook(every_n_steps=10)]
        cfg = stf.ConfigProto(loop_fusion_steps=8)
        fused0 = _fused_steps_count()
        n_calls = 0
        with stf.train.MonitoredSession(
                session_creator=stf.train.ChiefSessionCreator(config=cfg),
                hooks=hooks) as ms:
            while not ms.should_stop():
                ms.run(train, feed_dict=feed)
                n_calls += 1
            gs_val = int(np.asarray(
                ms.raw_session.variable_value("global_step")))
        assert gs_val == 25  # StopAtStepHook boundary respected exactly
        assert n_calls < 25  # windows actually fused multiple steps
        assert _fused_steps_count() > fused0

    def test_per_step_hook_forces_window_split(self):
        """A hook with the default until_next_trigger (needs every
        step) pins every window to 1 — nothing fuses."""
        train, loss, feed = self._model()

        class EveryStep(stf.train.SessionRunHook):
            observed = []

            def before_run(self, ctx):
                from simple_tensorflow_tpu.train.session_run_hook import \
                    SessionRunArgs

                return SessionRunArgs(
                    stf.train.get_global_step()._ref)

            def after_run(self, ctx, values):
                EveryStep.observed.append(int(np.asarray(values.results)))

        EveryStep.observed = []
        hooks = [stf.train.StopAtStepHook(last_step=5), EveryStep()]
        cfg = stf.ConfigProto(loop_fusion_steps=8)
        fused0 = _fused_steps_count()
        with stf.train.MonitoredSession(
                session_creator=stf.train.ChiefSessionCreator(config=cfg),
                hooks=hooks) as ms:
            while not ms.should_stop():
                ms.run(train, feed_dict=feed)
        # the gs read sits after the increment in this plan's order, so
        # each observation is the post-step value — and there is one
        # observation per STEP (no window ever fused)
        assert EveryStep.observed == [1, 2, 3, 4, 5]
        assert _fused_steps_count() == fused0  # every window split to 1

    def test_checkpoint_hook_splits_at_save_boundary(self, tmp_path):
        train, loss, feed = self._model()
        saver_hook = stf.train.CheckpointSaverHook(str(tmp_path),
                                                   save_steps=6)
        hooks = [stf.train.StopAtStepHook(last_step=14), saver_hook]
        cfg = stf.ConfigProto(loop_fusion_steps=64)
        with stf.train.MonitoredSession(
                session_creator=stf.train.ChiefSessionCreator(config=cfg),
                hooks=hooks) as ms:
            while not ms.should_stop():
                ms.run(train, feed_dict=feed)
            gs_val = int(np.asarray(
                ms.raw_session.variable_value("global_step")))
        assert gs_val == 14
        # the saver observed its step-6 boundaries (first trigger lands
        # on the first boundary after the initial save at step 0)
        from simple_tensorflow_tpu.train.saver import latest_checkpoint

        assert latest_checkpoint(str(tmp_path)) is not None

    def test_monitored_run_steps_api(self):
        train, loss, feed = self._model()
        cfg = stf.ConfigProto(loop_fusion_steps=16)
        with stf.train.MonitoredSession(
                session_creator=stf.train.ChiefSessionCreator(
                    config=cfg)) as ms:
            ms.run_steps(train, n=12, feed_dict=feed)
            gs_val = int(np.asarray(
                ms.raw_session.variable_value("global_step")))
        assert gs_val == 12


class TestConfig:
    def test_loop_fusion_steps_validation(self):
        with pytest.raises(ValueError, match="loop_fusion_steps"):
            stf.ConfigProto(loop_fusion_steps=0)

    def test_session_default_from_config(self):
        x = stf.placeholder(stf.float32, [2], name="x")
        v = stf.Variable(stf.zeros([2]), name="v")
        acc = stf.assign_add(v, x)
        sess = stf.Session(config=stf.ConfigProto(loop_fusion_steps=4))
        sess.run(stf.global_variables_initializer())
        out = sess.run_steps(acc, feed_dict={x: np.ones(2, np.float32)})
        np.testing.assert_array_equal(out, [4.0, 4.0])

    def test_output_mode_validation(self):
        x = stf.placeholder(stf.float32, [2], name="x")
        sess = stf.Session()
        with pytest.raises(ValueError, match="output_mode"):
            sess.run_steps(stf.identity(x), n=2, output_mode="bogus")
