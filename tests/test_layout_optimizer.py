"""Layout optimization pass (ref:
core/grappler/optimizers/layout_optimizer.cc).

An NCHW graph previously paid a transpose around EVERY conv/pool/bn at
lowering; the pass converts the ops to NHWC once and cancels interior
transpose pairs, leaving exactly the two boundary conversions."""

import json

import numpy as np
import pytest

import simple_tensorflow_tpu as stf
from simple_tensorflow_tpu.framework import graph_io, optimizer


def _build_nchw_block():
    """conv-bn-relu-conv-bn + identity shortcut + relu, all NCHW."""
    n, c, hw = 2, 8, 8
    x = stf.placeholder(stf.float32, [n, c, hw, hw], name="x")
    rng = np.random.RandomState(0)
    w1 = stf.constant(rng.randn(3, 3, c, c).astype(np.float32) * 0.2,
                      name="w1")
    w2 = stf.constant(rng.randn(3, 3, c, c).astype(np.float32) * 0.2,
                      name="w2")
    scale = stf.constant(np.ones(c, np.float32), name="scale")
    offset = stf.constant(np.zeros(c, np.float32), name="offset")

    h = stf.nn.conv2d(x, w1, strides=[1, 1, 1, 1], padding="SAME",
                      data_format="NCHW", name="conv1")
    h, _, _ = stf.nn.fused_batch_norm(h, scale, offset,
                                      data_format="NCHW", name="bn1")
    h = stf.nn.relu(h, name="relu1")
    h = stf.nn.conv2d(h, w2, strides=[1, 1, 1, 1], padding="SAME",
                      data_format="NCHW", name="conv2")
    h, _, _ = stf.nn.fused_batch_norm(h, scale, offset,
                                      data_format="NCHW", name="bn2")
    h = stf.add(h, x, name="residual")
    out = stf.nn.relu(h, name="block_out")
    return x, out, (n, c, hw)


def test_nchw_resnet_block_two_transposes():
    stf.reset_default_graph()
    x, out, (n, c, hw) = _build_nchw_block()
    gd = graph_io.graph_to_graphdef(stf.get_default_graph())

    opt = optimizer.optimize(gd, keep=[out.name])

    n_transpose = sum(1 for node in opt["node"]
                      if node["op"] == "Transpose")
    assert n_transpose == 2, (
        f"expected exactly 2 boundary transposes, got {n_transpose}: "
        f"{[nd['name'] for nd in opt['node'] if nd['op'] == 'Transpose']}")
    # every image op converted
    for node in opt["node"]:
        fmt = node.get("attr", {}).get("data_format")
        if fmt is not None:
            assert fmt == "NHWC", (node["name"], fmt)


def test_nchw_layout_rewrite_is_numerically_identical():
    stf.reset_default_graph()
    x, out, (n, c, hw) = _build_nchw_block()
    xv = np.random.RandomState(1).randn(n, c, hw, hw).astype(np.float32)
    sess = stf.Session()
    expected = sess.run(out, {x: xv})

    gd = graph_io.graph_to_graphdef(stf.get_default_graph())
    opt = optimizer.optimize(gd, keep=[out.name, x.name])

    stf.reset_default_graph()
    graph_io.import_graph_def(json.dumps(opt), name="")
    g = stf.get_default_graph()
    x2 = g.as_graph_element("x:0", allow_tensor=True,
                            allow_operation=False)
    out2 = g.as_graph_element(out.name, allow_tensor=True,
                              allow_operation=False)
    got = stf.Session().run(out2, {x2: xv})
    np.testing.assert_allclose(np.asarray(got), np.asarray(expected),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("seed", range(10))
def test_layout_rewrite_invariant_on_random_nchw_chains(seed):
    """Optimization-invariance fuzz: random NCHW conv/pool/bn/residual
    chains must compute identical values before and after the layout
    rewrite (arbitrary compositions of the push-down/cancellation
    phases, not just the hand-built block)."""
    rng = np.random.RandomState(400 + seed)
    stf.reset_default_graph()
    n, c, hw = 2, int(rng.choice([4, 8])), 8
    x = stf.placeholder(stf.float32, [n, c, hw, hw], name="x")
    h = x
    residual = None
    for k in range(int(rng.randint(3, 7))):
        choice = rng.choice(["conv", "pool", "bn", "relu", "bias",
                             "save", "res"])
        cur_c = int(h.shape[1])
        cur_hw = int(h.shape[2])
        if choice == "conv":
            w = stf.constant(rng.randn(3, 3, cur_c, cur_c)
                             .astype(np.float32) * 0.2)
            h = stf.nn.conv2d(h, w, strides=[1, 1, 1, 1],
                              padding="SAME", data_format="NCHW")
        elif choice == "pool" and cur_hw >= 4:
            op = (stf.nn.max_pool if rng.rand() < 0.5
                  else stf.nn.avg_pool)
            h = op(h, ksize=[1, 1, 2, 2], strides=[1, 1, 2, 2],
                   padding="SAME", data_format="NCHW")
            residual = None  # shape changed
        elif choice == "bn":
            h, _, _ = stf.nn.fused_batch_norm(
                h, stf.constant(np.ones(cur_c, np.float32)),
                stf.constant(np.zeros(cur_c, np.float32)),
                data_format="NCHW")
        elif choice == "relu":
            h = stf.nn.relu(h)
        elif choice == "bias":
            h = stf.nn.bias_add(
                h, stf.constant(rng.randn(cur_c).astype(np.float32)),
                data_format="NCHW")
        elif choice == "save":
            residual = h
        elif choice == "res" and residual is not None and \
                residual.shape.as_list() == h.shape.as_list():
            h = stf.add(h, residual)
    out = stf.reduce_mean(h, name=f"fz_out_{seed}")
    xv = rng.randn(n, c, hw, hw).astype(np.float32)
    with stf.Session() as sess:
        expected = np.asarray(sess.run(out, {x: xv}))

    gd = graph_io.graph_to_graphdef(stf.get_default_graph())
    opt = optimizer.optimize(gd, keep=[out.name, x.name])
    stf.reset_default_graph()
    graph_io.import_graph_def(json.dumps(opt), name="")
    g = stf.get_default_graph()
    x2 = g.as_graph_element("x:0", allow_tensor=True,
                            allow_operation=False)
    out2 = g.as_graph_element(out.name, allow_tensor=True,
                              allow_operation=False)
    with stf.Session() as sess2:
        got = np.asarray(sess2.run(out2, {x2: xv}))
    np.testing.assert_allclose(got, expected, rtol=1e-5, atol=1e-5)


def test_nchw_pool_converts():
    stf.reset_default_graph()
    x = stf.placeholder(stf.float32, [2, 4, 8, 8], name="xp")
    p = stf.nn.max_pool(x, ksize=[1, 1, 2, 2], strides=[1, 1, 2, 2],
                        padding="VALID", data_format="NCHW", name="pool")
    gd = graph_io.graph_to_graphdef(stf.get_default_graph())
    opt = optimizer.layout_optimization(gd, keep=[p.name, x.name])
    # name swap: "pool" is now the boundary transpose, the converted op
    # is "pool/nhwc" — by-name fetches still return NCHW data
    shim = next(nd for nd in opt["node"] if nd["name"] == "pool")
    assert shim["op"] == "Transpose"
    pool = next(nd for nd in opt["node"] if nd["name"] == "pool/nhwc")
    assert pool["attr"]["data_format"] == "NHWC"
    from simple_tensorflow_tpu.framework.graph_io import _decode_attr
    assert tuple(_decode_attr(pool["attr"]["ksize"])) == (1, 2, 2, 1)
    assert tuple(_decode_attr(pool["attr"]["strides"])) == (1, 2, 2, 1)
    # numerics
    xv = np.random.RandomState(2).randn(2, 4, 8, 8).astype(np.float32)
    stf.reset_default_graph()
    x1 = stf.placeholder(stf.float32, [2, 4, 8, 8], name="xo")
    p1 = stf.nn.max_pool(x1, ksize=[1, 1, 2, 2], strides=[1, 1, 2, 2],
                         padding="VALID", data_format="NCHW")
    expected = stf.Session().run(p1, {x1: xv})
    stf.reset_default_graph()
    graph_io.import_graph_def(json.dumps(opt), name="")
    g = stf.get_default_graph()
    got = stf.Session().run(
        g.as_graph_element(p.name, True, False),
        {g.as_graph_element("xp:0", True, False): xv})
    np.testing.assert_allclose(np.asarray(got), np.asarray(expected))


def test_nhwc_graph_untouched():
    stf.reset_default_graph()
    x = stf.placeholder(stf.float32, [2, 8, 8, 4], name="xn")
    w = stf.constant(np.ones((3, 3, 4, 4), np.float32), name="wn")
    y = stf.nn.conv2d(x, w, strides=[1, 1, 1, 1], padding="SAME",
                      name="convn")
    gd = graph_io.graph_to_graphdef(stf.get_default_graph())
    opt = optimizer.layout_optimization(gd, keep=[y.name, x.name])
    assert not any(nd["op"] == "Transpose" for nd in opt["node"])
    assert len(opt["node"]) == len(gd["node"])


class TestShapeMaterialization:
    """Constant folding through shape ops: Shape/
    Size/Rank of a statically-shaped producer folds to a Const even when
    the producer's VALUE isn't constant (grappler shape
    materialization)."""

    def test_graphdef_level(self):
        stf.reset_default_graph()
        x = stf.placeholder(stf.float32, [3, 5], name="sm_x")
        y = stf.multiply(x, 2.0, name="sm_y")  # non-const producer
        sh = stf.shape(y, name="sm_shape")
        sz = stf.size(y, name="sm_size")
        rk = stf.rank(y, name="sm_rank")
        gd = graph_io.graph_to_graphdef(stf.get_default_graph())
        opt = optimizer.constant_folding(gd)
        by_name = {n["name"]: n for n in opt["node"]}
        for name, expect in [("sm_shape", [3, 5]), ("sm_size", 15),
                             ("sm_rank", 2)]:
            node = by_name[name]
            assert node["op"] == "Const", (name, node["op"])
            val = graph_io._decode_attr(node["attr"]["value"])
            np.testing.assert_array_equal(np.asarray(val), expect)

    def test_session_plan_level(self):
        """The IR pass folds them out of the lowered step entirely."""
        from simple_tensorflow_tpu.framework import optimizer as opt_mod

        stf.reset_default_graph()
        x = stf.placeholder(stf.float32, [4, 2], name="sp_x")
        y = stf.tanh(x)
        s = stf.shape(y)
        fed = {x}
        from simple_tensorflow_tpu.framework import lowering

        plan = lowering.prune([s.op], fed)
        new_plan, const_env, _ = opt_mod.optimize_pruned(plan, fed, [s])
        assert s in const_env
        np.testing.assert_array_equal(const_env[s], [4, 2])
        assert all(op.type not in ("Shape",) for op in new_plan)
        # end-to-end through the session too
        sess = stf.Session()
        out = sess.run(s, {x: np.zeros((4, 2), np.float32)})
        np.testing.assert_array_equal(np.asarray(out), [4, 2])


def test_layout_keeps_multi_output_op_fetched_by_extra_output():
    """A FusedBatchNorm whose ':1' (batch mean) is externally fetched
    must not be converted — the single-output transpose shim cannot
    serve output 1 (r5 review fix)."""
    stf.reset_default_graph()
    x = stf.placeholder(stf.float32, [2, 4, 6, 6], name="mx")
    scale = stf.constant(np.ones(4, np.float32))
    offset = stf.constant(np.zeros(4, np.float32))
    y, mean, var = stf.nn.fused_batch_norm(x, scale, offset,
                                           data_format="NCHW", name="mbn")
    gd = graph_io.graph_to_graphdef(stf.get_default_graph())
    opt = optimizer.layout_optimization(gd, keep=[mean.name, x.name])
    bn = next(nd for nd in opt["node"] if nd["name"] == "mbn")
    assert bn["op"] == "FusedBatchNorm"  # left alone, not a shim
    assert bn["attr"]["data_format"] == "NCHW"
    # the kept ref still resolves after import
    stf.reset_default_graph()
    graph_io.import_graph_def(json.dumps(opt), name="")
    g = stf.get_default_graph()
    xv = np.random.RandomState(0).randn(2, 4, 6, 6).astype(np.float32)
    out = stf.Session().run(g.as_graph_element("mbn:1", True, False),
                            {g.as_graph_element("mx:0", True, False): xv})
    np.testing.assert_allclose(np.asarray(out),
                               xv.mean(axis=(0, 2, 3)), rtol=1e-4,
                               atol=1e-4)


# ---------------------------------------------------------------------------
# function-aware passes (PR 1 tentpole): layout/CSE/fold/DCE recurse into
# cond branches and while/scan bodies via the PassManager
# ---------------------------------------------------------------------------

def _bodies_of(gd):
    """{(node_name, attr): body_dict} over every FuncGraph in gd."""
    out = {}
    for node in gd["node"]:
        for d, b in optimizer._node_bodies(node):
            out[(node["name"], d["attr"])] = b
    return out


def _transposes(body):
    return [n for n in body["node"] if n["op"] == "Transpose"]


def _random_shape_preserving_chain(rng, h, c, stfm):
    """Random NCHW chain that keeps [n,c,hw,hw] (loop-carry safe).
    Always opens with a conv so every chain has layout work to cancel."""
    residual = None
    w0 = stfm.constant(rng.randn(3, 3, c, c).astype(np.float32) * 0.2)
    h = stfm.nn.conv2d(h, w0, strides=[1, 1, 1, 1], padding="SAME",
                       data_format="NCHW")
    for _ in range(int(rng.randint(2, 5))):
        choice = rng.choice(["conv", "bn", "relu", "bias", "save", "res"])
        if choice == "conv":
            w = stfm.constant(rng.randn(3, 3, c, c).astype(np.float32)
                              * 0.2)
            h = stfm.nn.conv2d(h, w, strides=[1, 1, 1, 1],
                               padding="SAME", data_format="NCHW")
        elif choice == "bn":
            h, _, _ = stfm.nn.fused_batch_norm(
                h, stfm.constant(np.ones(c, np.float32)),
                stfm.constant(np.zeros(c, np.float32)),
                data_format="NCHW")
        elif choice == "relu":
            h = stfm.nn.relu(h)
        elif choice == "bias":
            h = stfm.nn.bias_add(
                h, stfm.constant(rng.randn(c).astype(np.float32)),
                data_format="NCHW")
        elif choice == "save":
            residual = h
        elif choice == "res" and residual is not None:
            h = stfm.add(h, residual)
    return h


def _assert_no_transpose_pairs(body, where):
    """Zero interior transpose pairs: no transpose may consume another
    transpose's output (an adjacent inverse pair the pass missed)."""
    t_names = {n["name"] for n in _transposes(body)}
    for n in _transposes(body):
        for ref in n.get("input", []):
            src = ref.rsplit(":", 1)[0]
            assert src not in t_names, (
                f"{where}: interior transpose pair "
                f"{src} -> {n['name']} survived the pass")


@pytest.mark.parametrize("seed", range(6))
def test_layout_rewrite_invariant_in_cond_branches(seed):
    """Fuzz: random NCHW chains INSIDE cond branches must keep identical
    values through the pass, with zero interior transpose pairs and at
    most the two boundary conversions left in the branch."""
    rng = np.random.RandomState(700 + seed)
    stf.reset_default_graph()
    n, c, hw = 2, int(rng.choice([4, 8])), 8
    x = stf.placeholder(stf.float32, [n, c, hw, hw], name="cx")

    def branch_a():
        return _random_shape_preserving_chain(rng, x, c, stf)

    def branch_b():
        return _random_shape_preserving_chain(rng, x, c, stf)

    pred = stf.reduce_sum(x) > 0.0
    out = stf.cond(pred, branch_a, branch_b)
    res = stf.reduce_mean(out, name=f"cond_fz_{seed}")
    xv = rng.randn(n, c, hw, hw).astype(np.float32)
    with stf.Session() as sess:
        exp_pos = np.asarray(sess.run(res, {x: np.abs(xv)}))
        exp_neg = np.asarray(sess.run(res, {x: -np.abs(xv)}))

    gd = graph_io.graph_to_graphdef(stf.get_default_graph())
    opt = optimizer.optimize(gd, keep=[res.name, x.name])
    for (node, attr), body in _bodies_of(opt).items():
        assert len(_transposes(body)) <= 2, (
            node, attr, [t["name"] for t in _transposes(body)])
        _assert_no_transpose_pairs(body, f"{node}.{attr}")
        for nd in body["node"]:
            fmt = nd.get("attr", {}).get("data_format")
            if fmt is not None:
                assert fmt == "NHWC", (nd["name"], fmt)

    stf.reset_default_graph()
    graph_io.import_graph_def(json.dumps(opt), name="")
    g = stf.get_default_graph()
    x2 = g.as_graph_element("cx:0", True, False)
    r2 = g.as_graph_element(res.name, True, False)
    with stf.Session() as s2:
        np.testing.assert_allclose(
            np.asarray(s2.run(r2, {x2: np.abs(xv)})), exp_pos,
            rtol=1e-5, atol=1e-5)
        np.testing.assert_allclose(
            np.asarray(s2.run(r2, {x2: -np.abs(xv)})), exp_neg,
            rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("seed", range(6))
def test_layout_rewrite_invariant_in_while_bodies(seed):
    """Fuzz: random shape-preserving NCHW chains inside while bodies.
    After the pass the BODY must contain zero transposes — the boundary
    pair is pushed outside the loop (layout invariance across the
    iteration is what licenses the push), so per-iteration transpose
    cost is zero."""
    rng = np.random.RandomState(800 + seed)
    stf.reset_default_graph()
    n, c, hw = 2, int(rng.choice([4, 8])), 8
    x = stf.placeholder(stf.float32, [n, c, hw, hw], name="wx")
    i0 = stf.constant(0, name="wi0")
    trip = int(rng.randint(2, 5))

    def cond_fn(i, h):
        return i < trip

    def body_fn(i, h):
        return i + 1, _random_shape_preserving_chain(rng, h, c, stf)

    _, h_out = stf.while_loop(cond_fn, body_fn, [i0, x])
    res = stf.reduce_mean(h_out, name=f"while_fz_{seed}")
    xv = rng.randn(n, c, hw, hw).astype(np.float32)
    with stf.Session() as sess:
        expected = np.asarray(sess.run(res, {x: xv}))

    gd = graph_io.graph_to_graphdef(stf.get_default_graph())
    opt = optimizer.optimize(gd, keep=[res.name, x.name])
    for (node, attr), body in _bodies_of(opt).items():
        if attr == "body_graph":
            assert not _transposes(body), (
                node, [t["name"] for t in _transposes(body)])
        _assert_no_transpose_pairs(body, f"{node}.{attr}")
    # the conversion pair moved OUTSIDE the loop: exactly one in, one out
    outer_t = [nd for nd in opt["node"] if nd["op"] == "Transpose"]
    assert len(outer_t) == 2, [t["name"] for t in outer_t]

    stf.reset_default_graph()
    graph_io.import_graph_def(json.dumps(opt), name="")
    g = stf.get_default_graph()
    x2 = g.as_graph_element("wx:0", True, False)
    r2 = g.as_graph_element(res.name, True, False)
    with stf.Session() as s2:
        got = np.asarray(s2.run(r2, {x2: xv}))
    np.testing.assert_allclose(got, expected, rtol=1e-4, atol=1e-5)


class TestFunctionAwarePasses:
    """CSE/fold/LICM/DCE descend into bodies (tentpole acceptance)."""

    def test_cse_and_fold_fire_inside_scan_body(self):
        stf.reset_default_graph()
        k = stf.constant(3.0, name="sk")
        e = stf.placeholder(stf.float32, [5, 2], name="se")

        def fn(acc, xel):
            a = stf.exp(xel)
            b = stf.exp(xel)      # duplicate: must CSE inside the body
            c2 = k * 2.0          # captured const: must fold inside
            return acc + a + b + c2

        out = stf.scan(fn, e, initializer=stf.constant(
            np.zeros(2, np.float32)))
        res = stf.identity(out[-1], name="scan_cse_res")
        gd = graph_io.graph_to_graphdef(stf.get_default_graph())
        before = _bodies_of(gd)[next(
            kk for kk in _bodies_of(gd) if kk[1] == "body")]
        n_exp_before = sum(1 for nd in before["node"]
                           if nd["op"] == "Exp")
        assert n_exp_before == 2
        opt = optimizer.optimize(gd, keep=[res.name, e.name],
                                 layout=False)
        body = _bodies_of(opt)[next(
            kk for kk in _bodies_of(opt) if kk[1] == "body")]
        ops = [nd["op"] for nd in body["node"]]
        assert ops.count("Exp") == 1, ops   # CSE fired in-body
        assert ops.count("Mul") == 0, ops   # k*2 folded in-body
        assert len(body["node"]) < len(before["node"])
        # numerics preserved
        ev = np.random.RandomState(3).randn(5, 2).astype(np.float32)
        stf.reset_default_graph()
        graph_io.import_graph_def(json.dumps(opt), name="")
        g = stf.get_default_graph()
        got = stf.Session().run(
            g.as_graph_element(res.name, True, False),
            {g.as_graph_element("se:0", True, False): ev})
        expected = np.zeros(2, np.float32)
        for row in ev:
            expected = expected + 2 * np.exp(row) + 6.0
        np.testing.assert_allclose(np.asarray(got), expected, rtol=1e-4)

    def test_licm_hoists_invariant_expr_out_of_while_body(self):
        stf.reset_default_graph()
        v = stf.placeholder(stf.float32, [8], name="hv")
        i0 = stf.constant(0)
        acc0 = stf.constant(np.zeros(8, np.float32))

        def body(i, acc):
            inv = stf.tanh(v) * 3.0  # depends only on the capture
            return i + 1, acc + inv

        _, acc = stf.while_loop(lambda i, a: i < 4, body, [i0, acc0])
        res = stf.identity(acc, name="licm_res")
        gd = graph_io.graph_to_graphdef(stf.get_default_graph())
        opt = optimizer.optimize(gd, keep=[res.name, v.name],
                                 layout=False)
        body_d = _bodies_of(opt)[next(
            kk for kk in _bodies_of(opt) if kk[1] == "body_graph")]
        ops = [nd["op"] for nd in body_d["node"]]
        assert "Tanh" not in ops and "Mul" not in ops, ops
        hoisted = [nd for nd in opt["node"] if "/licm/" in nd["name"]]
        assert any(nd["op"] == "Tanh" for nd in hoisted)
        assert any(nd["op"] == "Mul" for nd in hoisted)
        # value-invariance after the hoist
        vv = np.random.RandomState(4).randn(8).astype(np.float32)
        stf.reset_default_graph()
        graph_io.import_graph_def(json.dumps(opt), name="")
        g = stf.get_default_graph()
        got = stf.Session().run(
            g.as_graph_element(res.name, True, False),
            {g.as_graph_element("hv:0", True, False): vv})
        np.testing.assert_allclose(np.asarray(got),
                                   4 * np.tanh(vv) * 3.0, rtol=1e-5)

    def test_session_plan_optimizes_bodies(self):
        """The IR-level pass (Session hot path) records an optimized
        per-plan body plan in func_plans: in-body CSE means one Exp
        lowers per iteration, not two."""
        from simple_tensorflow_tpu.framework import lowering as lmod
        from simple_tensorflow_tpu.framework import optimizer as omod

        stf.reset_default_graph()
        e = stf.placeholder(stf.float32, [4, 2], name="pe")

        def fn(acc, xel):
            return acc + stf.exp(xel) + stf.exp(xel)

        out = stf.scan(fn, e, initializer=stf.constant(
            np.zeros(2, np.float32)))
        res = out[-1]
        pruned = lmod.prune([res.op], {e})
        func_plans = {}
        omod.optimize_pruned(pruned, {e}, [res], func_plans=func_plans)
        scan_op = next(op for op in pruned if op.type == "Scan")
        fg = scan_op.attrs["body"]
        plan_ops, _, alias = func_plans[fg]
        assert sum(1 for o in plan_ops if o.type == "Exp") == 1
        assert alias  # the duplicate resolves through the alias map
        # and the session end-to-end still computes the right thing
        ev = np.random.RandomState(5).randn(4, 2).astype(np.float32)
        sess = stf.Session()
        got = sess.run(res, {e: ev})
        np.testing.assert_allclose(
            np.asarray(got),
            np.sum(2 * np.exp(ev), axis=0), rtol=1e-4)
        step = next(iter(sess._cache.values()))
        assert fg in step.func_plans

    def test_feeding_a_captured_const_overrides_body_seed(self):
        """Feeding a tensor captured by a loop body must override the
        graph-time constant — body plans are per-(fetches, feeds), so a
        baked-in capture const from one plan can never leak into a run
        that feeds it (r1 review fix)."""
        stf.reset_default_graph()
        c = stf.constant(2.0, name="fc")
        elems = stf.constant(np.ones(3, np.float32))
        out = stf.foldl(lambda carry, e: carry * (c + 1.0), elems,
                        initializer=stf.constant(1.0))
        sess = stf.Session()
        np.testing.assert_allclose(float(sess.run(out)), 27.0)
        np.testing.assert_allclose(float(sess.run(out, {c: 5.0})), 216.0)
        # and the unfed plan is untouched by the fed one
        np.testing.assert_allclose(float(sess.run(out)), 27.0)

    def test_optimize_graph_functions_inplace(self):
        """Live-graph body rewrite: signature preserved, values
        unchanged, rewrite version bumped so session caches invalidate."""
        from simple_tensorflow_tpu.framework import optimizer as omod

        stf.reset_default_graph()
        rng = np.random.RandomState(0)
        x = stf.placeholder(stf.float32, [2, 4, 8, 8], name="ix")
        w = stf.constant(rng.randn(3, 3, 4, 4).astype(np.float32) * 0.2)

        def bt():
            h = stf.nn.conv2d(x, w, strides=[1, 1, 1, 1],
                              padding="SAME", data_format="NCHW")
            return stf.nn.relu(h)

        out = stf.cond(stf.reduce_sum(x) > 0.0, bt, lambda: x * 2.0)
        res = stf.reduce_mean(out, name="ir")
        g = stf.get_default_graph()
        xv = np.abs(rng.randn(2, 4, 8, 8)).astype(np.float32)
        sess = stf.Session()
        before = sess.run(res, {x: xv})
        v0 = g.rewrite_version
        key0 = sess._cache_key([res], {x})
        assert omod.optimize_graph_functions(g) >= 1
        assert g.rewrite_version == v0 + 1
        assert sess._cache_key([res], {x}) != key0
        after = sess.run(res, {x: xv})
        np.testing.assert_allclose(after, before, rtol=1e-5)
        cond_op = next(op for op in g.get_operations()
                       if op.type == "Cond")
        tg = cond_op.attrs["true_graph"]
        fmts = [op.attrs.get("data_format")
                for op in tg.get_operations()
                if "data_format" in op.attrs]
        assert fmts and all(f == "NHWC" for f in fmts)
        n_t = sum(1 for op in tg.get_operations()
                  if op.type == "Transpose")
        assert n_t == 2, n_t

    def test_cost_model_attributes_into_loop_bodies(self):
        """A conv inside a scan body is costed per ITERATION — the flat
        walk priced it at ~0 ('cost attribution into
        bodies so the win is measurable')."""
        from simple_tensorflow_tpu.framework import cost_model

        stf.reset_default_graph()
        rng = np.random.RandomState(1)
        steps = 6
        x = stf.placeholder(stf.float32, [2, 8, 8, 4], name="ce")
        w = stf.constant(rng.randn(3, 3, 4, 4).astype(np.float32))
        dummy = stf.constant(np.zeros((steps, 1), np.float32))

        def fn(carry, _):
            return stf.nn.relu(stf.nn.conv2d(
                carry, w, strides=[1, 1, 1, 1], padding="SAME"))

        out = stf.scan(fn, dummy, initializer=x)
        res = stf.reduce_mean(out[-1])
        est = cost_model.estimate(res, feeds=[x])
        # one conv ≈ 2*out_elems*kh*kw*cin = 2*(2*8*8*4)*3*3*4 ≈ 73k
        one_conv = 2.0 * (2 * 8 * 8 * 4) * 3 * 3 * 4
        assert est.flops >= steps * one_conv, (
            f"in-body conv not multiplied by trip: {est.flops} < "
            f"{steps * one_conv}")


def test_shape_fold_honors_out_type():
    """out_type is honored through the documented 64-bit narrowing
    policy: the folded constant carries the SAME dtype the runtime
    pure_fn computes (int32 with x64 off, int64 with it on) — folding
    must never change an observable dtype."""
    from simple_tensorflow_tpu.framework import dtypes as dtypes_mod

    stf.reset_default_graph()
    x = stf.placeholder(stf.float32, [3, 5], name="ot_x")
    y = stf.multiply(x, 2.0)
    sh = stf.shape(y, out_type=stf.int64, name="ot_shape")
    gd = graph_io.graph_to_graphdef(stf.get_default_graph())
    opt = optimizer.constant_folding(gd)
    node = next(nd for nd in opt["node"] if nd["name"] == "ot_shape")
    assert node["op"] == "Const"
    val = graph_io._decode_attr(node["attr"]["value"])
    expect_dt = dtypes_mod.narrowed_if_no_x64(stf.int64).np_dtype
    assert np.asarray(val).dtype == expect_dt
    np.testing.assert_array_equal(np.asarray(val), [3, 5])
