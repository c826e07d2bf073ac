"""Plan-time graph optimizer on the Session hot path (
fold/CSE/DCE must actually run in _plan) + device-scope placement."""

import numpy as np
import pytest

import simple_tensorflow_tpu as stf


@pytest.fixture(autouse=True)
def fresh_graph():
    stf.reset_default_graph()
    yield


def _only_step(sess):
    steps = list(sess._cache.values())
    assert len(steps) == 1
    return steps[0]


class TestPlanTimeFolding:
    def test_const_subgraph_folds_to_fewer_device_ops(self):
        x = stf.placeholder(stf.float32, [2], name="x")
        # (2*3)+4 is a 3-op constant subtree; after folding the device
        # program should contain just the final Add on x.
        c = stf.add(stf.multiply(stf.constant(2.0), stf.constant(3.0)),
                    stf.constant(4.0))
        y = stf.add(x, c)
        with stf.Session() as sess:
            out = sess.run(y, {x: np.float32([1.0, 2.0])})
            step = _only_step(sess)
        assert out.tolist() == [11.0, 12.0]
        assert step.const_env  # something folded at plan time
        assert len(step.device_ops) == 1, [o.type for o in step.device_ops]
        assert step.device_ops[0].type == "Add"

    def test_fetch_of_fully_folded_value(self):
        y = stf.multiply(stf.constant(6.0), stf.constant(7.0))
        with stf.Session() as sess:
            out = sess.run(y)
            step = _only_step(sess)
        assert float(out) == 42.0
        assert not step.has_device_stage  # nothing left to compile

    def test_cse_merges_duplicate_pure_ops(self):
        x = stf.placeholder(stf.float32, [3], name="x")
        y = stf.add(stf.exp(x), stf.exp(x))  # two distinct Exp nodes
        with stf.Session() as sess:
            v = np.float32([0.0, 1.0, 2.0])
            out = sess.run(y, {x: v})
            step = _only_step(sess)
        assert np.allclose(out, 2.0 * np.exp(v), rtol=1e-5)
        assert sum(1 for o in step.device_ops if o.type == "Exp") == 1
        assert step.alias  # duplicate was aliased, not traced

    def test_fold_does_not_touch_random_or_variables(self):
        v = stf.Variable(stf.constant([1.0, 2.0]), name="nv")
        r = stf.random_normal([2], seed=1)
        y = v.value() + r
        with stf.Session() as sess:
            sess.run(stf.global_variables_initializer())
            a = np.asarray(sess.run(y))
            b = np.asarray(sess.run(y))
        assert a.shape == (2,)
        assert not np.array_equal(a, b)  # rng still advances per run

    def test_gradients_through_cse_and_folding(self):
        x = stf.placeholder(stf.float32, [2], name="x")
        k = stf.multiply(stf.constant(2.0), stf.constant(1.5))  # folds to 3
        y = stf.reduce_sum(stf.square(x) * k + stf.square(x))
        (gx,) = stf.gradients(y, [x])
        with stf.Session() as sess:
            g = sess.run(gx, {x: np.float32([1.0, 2.0])})
        # d/dx (3x^2 + x^2) = 8x
        assert np.allclose(g, [8.0, 16.0], rtol=1e-5)


class TestDeviceScopePlacement:
    def test_cpu_scope_pins_op_to_host_stage(self):
        x = stf.placeholder(stf.float32, [2], name="x")
        with stf.device("/cpu:0"):
            h = stf.add(x, stf.constant(1.0), name="host_add")
        y = stf.multiply(h, stf.constant(2.0))
        with stf.Session() as sess:
            out = sess.run(y, {x: np.float32([1.0, 2.0])})
            step = _only_step(sess)
        assert out.tolist() == [4.0, 6.0]
        host_types = [o.name for o in step.host_plan]
        assert any("host_add" in n for n in host_types), host_types
        assert all("host_add" not in o.name for o in step.device_ops)

    def test_device_scope_recorded_on_op(self):
        with stf.device("/device:CPU:0"):
            c = stf.add(stf.constant(1.0), stf.constant(2.0), name="dev_rec")
        assert "CPU" in c.op.device

    def test_tpu_scope_stays_in_device_stage(self):
        x = stf.placeholder(stf.float32, [2], name="x")
        with stf.device("/device:TPU:0"):
            y = stf.add(x, stf.constant(1.0), name="tpu_add")
        with stf.Session() as sess:
            out = sess.run(y, {x: np.float32([0.0, 1.0])})
            step = _only_step(sess)
        assert out.tolist() == [1.0, 2.0]
        assert any("tpu_add" in o.name for o in step.device_ops)

    def test_host_pinned_consumer_of_device_result(self):
        x = stf.placeholder(stf.float32, [2], name="x")
        dev = stf.square(x)  # device stage
        with stf.device("/cpu:0"):
            post = stf.add(dev, stf.constant(1.0), name="post_add")
        with stf.Session() as sess:
            out = sess.run(post, {x: np.float32([2.0, 3.0])})
            step = _only_step(sess)
        assert out.tolist() == [5.0, 10.0]
        assert any("post_add" in o.name for o in step.post_host_plan)
