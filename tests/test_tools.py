"""Serving-story tools: Estimator.export_savedmodel, freeze_graph,
inspect_checkpoint, strip_unused, optimize_for_inference
(ref: python/tools/{freeze_graph,inspect_checkpoint,strip_unused,
optimize_for_inference}.py, estimator export path)."""

import io
import json
import os

import numpy as np
import pytest

import simple_tensorflow_tpu as stf
from simple_tensorflow_tpu.framework import graph_io
from simple_tensorflow_tpu import tools


def _train_small_model(tmp_path):
    """Train y = x @ w + b briefly; save checkpoint + graph; return paths
    and the final weights."""
    stf.reset_default_graph()
    rng = np.random.RandomState(0)
    X = rng.rand(64, 3).astype(np.float32)
    W_true = np.float32([[1.0], [-2.0], [0.5]])
    Y = X @ W_true

    x = stf.placeholder(stf.float32, [None, 3], name="x")
    w = stf.Variable(np.zeros((3, 1), np.float32), name="w")
    b = stf.Variable(np.zeros((1,), np.float32), name="b")
    pred = stf.add(stf.matmul(x, w), b, name="pred")
    y = stf.placeholder(stf.float32, [None, 1], name="y")
    loss = stf.reduce_mean(stf.square(pred - y))
    train_op = stf.train.GradientDescentOptimizer(0.5).minimize(loss)

    sess = stf.Session()
    sess.run(stf.global_variables_initializer())
    for _ in range(60):
        sess.run(train_op, {x: X, y: Y})
    w_val, b_val = sess.run([w, b])
    ckpt = stf.train.Saver().save(sess, str(tmp_path / "model"),
                                  global_step=60)
    gd = graph_io.graph_to_graphdef(stf.get_default_graph())
    graph_path = str(tmp_path / "graph.json")
    with open(graph_path, "w") as f:
        json.dump(gd, f)
    return graph_path, ckpt, w_val, b_val, X, Y


class TestFreezeGraph:
    def test_freeze_and_run_without_checkpoint(self, tmp_path):
        graph_path, ckpt, w_val, b_val, X, Y = _train_small_model(tmp_path)
        frozen_path = str(tmp_path / "frozen.json")
        frozen = tools.freeze_graph(graph_path, ckpt, "pred",
                                    output_graph=frozen_path)
        ops = {n["op"] for n in frozen["node"]}
        assert "VariableV2" not in ops and "ReadVariable" not in ops
        assert "Assign" not in ops  # optimizer/init machinery pruned

        # import the frozen graph into a fresh graph and run WITHOUT any
        # variable initialization or restore
        stf.reset_default_graph()
        with open(frozen_path) as f:
            frozen_loaded = json.load(f)
        (pred_t,) = graph_io.import_graph_def(
            frozen_loaded, return_elements=["pred:0"], name="")
        x_t = stf.get_default_graph().as_graph_element("x:0")
        with stf.Session() as sess:
            out = sess.run(pred_t, {x_t: X})
        np.testing.assert_allclose(out, X @ w_val + b_val, rtol=1e-5)
        np.testing.assert_allclose(out, Y, atol=0.15)  # it did train

    def test_missing_variable_raises(self, tmp_path):
        graph_path, ckpt, *_ = _train_small_model(tmp_path)
        with open(graph_path) as f:
            gd = json.load(f)
        with pytest.raises(ValueError, match="not in"):
            tools.freeze_graph_def(gd, {"only_this": np.zeros(1)}, "pred")


class TestInspectCheckpoint:
    def test_lists_tensors(self, tmp_path):
        _, ckpt, w_val, b_val, _, _ = _train_small_model(tmp_path)
        buf = io.StringIO()
        tensors = tools.print_tensors_in_checkpoint_file(ckpt, out=buf)
        listing = buf.getvalue()
        assert "w" in tensors and "b" in tensors
        assert "dtype=float32" in listing and "shape=[3, 1]" in listing
        np.testing.assert_allclose(tensors["w"], w_val)

    def test_single_tensor_with_values(self, tmp_path):
        _, ckpt, w_val, _, _, _ = _train_small_model(tmp_path)
        buf = io.StringIO()
        out = tools.print_tensors_in_checkpoint_file(
            ckpt, tensor_name="w", out=buf)
        assert list(out) == ["w"]
        assert str(float(w_val[0, 0]))[:4] in buf.getvalue()


class TestCkptInspectCLI:
    """ISSUE 10 satellite: ``python -m simple_tensorflow_tpu.tools.
    ckpt_inspect <dir>`` lists checkpoints, tensors/shapes/shardings,
    verifies checksums, and exits 1 on corruption."""

    def _checkpoint_dir(self, tmp_path):
        import simple_tensorflow_tpu as stf
        from simple_tensorflow_tpu import checkpoint as ckpt_mod

        stf.reset_default_graph()
        stf.Variable(stf.constant(np.ones((4, 2), np.float32)),
                     name="ci/kernel")
        gs = stf.train.get_or_create_global_step()
        sess = stf.Session()
        sess.run(stf.global_variables_initializer())
        d = str(tmp_path / "ckpts")
        mgr = ckpt_mod.CheckpointManager(d, max_to_keep=3)
        mgr.save(sess, global_step=3, blocking=True)
        mgr.save(sess, global_step=7, blocking=True)
        return d, mgr

    def test_lists_and_verifies_in_process(self, tmp_path):
        d, mgr = self._checkpoint_dir(tmp_path)
        from simple_tensorflow_tpu.tools import ckpt_inspect

        out = io.StringIO()
        rc = ckpt_inspect.run(d, tensors=True, out=out)
        text = out.getvalue()
        assert rc == 0
        assert "step=3" in text and "step=7" in text
        assert "ci/kernel  dtype=float32 shape=[4, 2]" in text
        assert "all verified" in text
        # --json shape
        out = io.StringIO()
        rc = ckpt_inspect.run(d, as_json=True, out=out)
        doc = json.loads(out.getvalue())
        assert rc == 0 and doc["ok"]
        assert [c["step"] for c in doc["checkpoints"]] == [3, 7]
        assert doc["checkpoints"][0]["host_state"][
            "rng_run_counter"] is not None

    def test_cli_subprocess_exit_codes(self, tmp_path):
        import subprocess
        import sys

        d, mgr = self._checkpoint_dir(tmp_path)
        env = dict(os.environ)
        env["JAX_PLATFORMS"] = "cpu"
        repo_root = os.path.dirname(os.path.dirname(
            os.path.abspath(__file__)))
        env["PYTHONPATH"] = repo_root + os.pathsep + env.get(
            "PYTHONPATH", "")
        cmd = [sys.executable, "-m",
               "simple_tensorflow_tpu.tools.ckpt_inspect", d]
        proc = subprocess.run(cmd, capture_output=True, text=True,
                              timeout=300, env=env)
        assert proc.returncode == 0, proc.stderr[-2000:]
        assert "all verified" in proc.stdout
        # flip one byte -> CORRUPT + exit 1
        latest = mgr.latest_checkpoint
        with open(latest + ".stfz", "r+b") as f:
            f.seek(25)
            b = f.read(1)
            f.seek(25)
            f.write(bytes([b[0] ^ 0xFF]))
        proc = subprocess.run(cmd, capture_output=True, text=True,
                              timeout=300, env=env)
        assert proc.returncode == 1, proc.stdout
        assert "CORRUPT" in proc.stdout
        assert "checksum" in proc.stdout

    def test_empty_dir_exits_nonzero(self, tmp_path):
        from simple_tensorflow_tpu.tools import ckpt_inspect

        out = io.StringIO()
        assert ckpt_inspect.run(str(tmp_path), out=out) == 1
        assert "no checkpoints found" in out.getvalue()


class TestStripUnused:
    def test_prunes_to_subgraph(self, tmp_path):
        graph_path, ckpt, *_ = _train_small_model(tmp_path)
        frozen = tools.freeze_graph(graph_path, ckpt, "pred")
        # strip with x as the input: everything else (y, loss, grads chain
        # leftovers) must be gone
        stripped = tools.strip_unused_nodes(frozen, "x", "pred")
        names = {n["name"] for n in stripped["node"]}
        assert "pred" in names and "x" in names
        assert not any("grad" in n or n == "y" for n in names), names
        x_node = next(n for n in stripped["node"] if n["name"] == "x")
        assert x_node["op"] == "Placeholder"

    def test_missing_input_raises(self, tmp_path):
        graph_path, ckpt, *_ = _train_small_model(tmp_path)
        frozen = tools.freeze_graph(graph_path, ckpt, "pred")
        with pytest.raises(ValueError, match="not in graph"):
            tools.strip_unused_nodes(frozen, "nope", "pred")


class TestOptimizeForInference:
    def test_folds_frozen_conv_bn(self, tmp_path):
        stf.reset_default_graph()
        rng = np.random.RandomState(1)
        x = stf.placeholder(stf.float32, [2, 8, 8, 3], name="img")
        h = stf.layers.conv2d(x, 4, 3, padding="same", use_bias=False,
                              name="c1")
        # inference-mode BN: running stats become Consts after freezing
        h = stf.layers.batch_normalization(h, training=False, fused=True,
                                           name="bn1")
        out = stf.identity(h, name="out")
        sess = stf.Session()
        sess.run(stf.global_variables_initializer())
        # give the stats non-trivial values so folding is actually tested
        for vname, val in [("bn1/moving_mean", rng.rand(4)),
                           ("bn1/moving_variance", 1.0 + rng.rand(4)),
                           ("bn1/gamma", 1.0 + 0.3 * rng.rand(4)),
                           ("bn1/beta", rng.rand(4))]:
            var = [v for v in stf.global_variables()
                   if v.var_name == vname][0]
            sess.run(stf.assign(var, val.astype(np.float32)))
        img = rng.rand(2, 8, 8, 3).astype(np.float32)
        ref = sess.run(out, {x: img})
        ckpt = stf.train.Saver().save(sess, str(tmp_path / "m"))
        gd = graph_io.graph_to_graphdef(stf.get_default_graph())

        frozen = tools.freeze_graph_def(
            gd, {k.replace("|", "/"): v
                 for k, v in np.load(ckpt + ".stfz").items()}, "out")
        opt = tools.optimize_for_inference(frozen, "img", "out")
        ops = [n["op"] for n in opt["node"]]
        assert "FusedBatchNorm" not in ops, ops
        # pass-through removal: the only Identity left is the protected
        # output node itself
        identities = [n["name"] for n in opt["node"]
                      if n["op"] == "Identity"]
        assert identities == ["out"], identities
        assert "BiasAdd" in ops and "Conv2D" in ops

        stf.reset_default_graph()
        (out_t,) = graph_io.import_graph_def(opt, return_elements=["out:0"],
                                             name="")
        x_t = stf.get_default_graph().as_graph_element("img:0")
        with stf.Session() as s2:
            folded = s2.run(out_t, {x_t: img})
        np.testing.assert_allclose(folded, ref, rtol=1e-4, atol=1e-5)


class TestEstimatorExport:
    def _model_fn(self, features, labels, mode, params=None):
        from simple_tensorflow_tpu import estimator as est

        w = stf.get_variable("w", [2, 1], initializer=stf.zeros_initializer())
        pred = stf.matmul(features["x"], w)
        if mode == est.ModeKeys.PREDICT:
            return est.EstimatorSpec(mode, predictions={"pred": pred})
        loss = stf.reduce_mean(stf.square(pred - labels))
        gs = stf.train.get_or_create_global_step()
        train_op = stf.train.GradientDescentOptimizer(0.2).minimize(
            loss, global_step=gs)
        return est.EstimatorSpec(mode, loss=loss, train_op=train_op,
                                 predictions={"pred": pred})

    def test_export_load_predict_roundtrip(self, tmp_path):
        from simple_tensorflow_tpu import estimator as est
        from simple_tensorflow_tpu import saved_model as sm

        rng = np.random.RandomState(0)
        X = rng.rand(32, 2).astype(np.float32)
        Y = X @ np.float32([[1.0], [2.0]])

        def input_fn():
            from simple_tensorflow_tpu import data as stf_data

            ds = stf_data.Dataset.from_tensor_slices(
                {"x": X, "y": Y}).repeat().batch(8)
            f = ds.make_one_shot_iterator().get_next()
            return {"x": f["x"]}, f["y"]

        e = est.Estimator(self._model_fn, model_dir=str(tmp_path / "md"))
        e.train(input_fn, steps=50)

        receiver_fn = est.build_raw_serving_input_receiver_fn(
            {"x": ([None, 2], stf.float32)})
        export_dir = e.export_savedmodel(str(tmp_path / "export"),
                                         receiver_fn)
        assert os.path.isdir(export_dir)

        # load the SavedModel in a fresh graph and serve
        stf.reset_default_graph()
        with stf.Session() as sess:
            meta = sm.load(sess, [sm.tag_constants.SERVING], export_dir)
            sig = meta["signature_def"][
                sm.signature_constants.DEFAULT_SERVING_SIGNATURE_DEF_KEY]
            x_name = sig["inputs"]["x"]["name"]
            pred_name = sig["outputs"]["pred"]["name"]
            out = sess.run(pred_name, {x_name: X})
        np.testing.assert_allclose(out, Y, atol=0.2)


def test_remove_training_nodes_follows_control_deps(tmp_path):
    """Control deps on a spliced-out Identity must redirect to its
    producer, not dangle (would fail the prune)."""
    from simple_tensorflow_tpu.tools.optimize_for_inference import (
        remove_training_nodes)

    stf.reset_default_graph()
    x = stf.placeholder(stf.float32, [2], name="cx")
    a = stf.identity(x, name="id1")
    g = stf.get_default_graph()
    with g.control_dependencies([a.op]):
        out = stf.add(x, stf.constant(np.float32([1, 1])), name="cout")
    gd = graph_io.graph_to_graphdef(g)
    cleaned = remove_training_nodes(gd, protected=["cout"])
    names = {n["name"] for n in cleaned["node"]}
    assert "id1" not in names
    cout = next(n for n in cleaned["node"] if n["name"] == "cout")
    assert all(c in names for c in cout["control_input"]), cout
    # and the prune that optimize_for_inference runs afterwards succeeds
    from simple_tensorflow_tpu.tools import graph_rewrite as gr

    pruned = gr.prune_to(cleaned, ["cout"])
    assert "cout" in {n["name"] for n in pruned["node"]}


class TestDebugAnalyzerCLI:
    """tfdbg-style CLI (ref: python/debug/cli/analyzer_cli.py) driven
    programmatically through run_command."""

    def _make_dump(self, tmp_path):
        from simple_tensorflow_tpu import debug as stf_debug

        stf.reset_default_graph()
        x = stf.placeholder(stf.float32, [2, 2], name="cli_x")
        y = stf.square(x, name="cli_sq")
        z = stf.reduce_sum(y, name="cli_sum")
        sess = stf.Session()
        wrapped = stf_debug.DumpingDebugWrapperSession(
            sess, str(tmp_path / "dumps"))
        wrapped.run(z, {x: np.array([[1., 2.], [3., np.inf]], np.float32)})
        return stf_debug.AnalyzerCLI(
            stf_debug.DebugDumpDir(str(tmp_path / "dumps")),
            graph=stf.get_default_graph())

    def test_lt_pt_runs_nan(self, tmp_path):
        cli = self._make_dump(tmp_path)
        lt = cli.run_command("lt")
        assert "cli_sq" in lt and "shape=(2, 2)" in lt
        assert "run_1" in cli.run_command("runs")
        pt = cli.run_command("pt cli_sq:0")
        assert "dtype=float32" in pt and "9." in pt
        pt_sliced = cli.run_command("pt cli_sq:0 -s [0]")
        assert "1." in pt_sliced and "4." in pt_sliced
        nan = cli.run_command("nan")
        assert "cli_sq" in nan or "cli_sum" in nan  # inf propagates

    def test_node_topology_commands(self, tmp_path):
        cli = self._make_dump(tmp_path)
        ni = cli.run_command("ni cli_sq")
        assert "op: Square" in ni and "cli_x" in ni
        li = cli.run_command("li cli_sq")
        assert "cli_x:0" in li
        lo = cli.run_command("lo cli_sq")
        assert "cli_sum" in lo

    def test_errors_and_aliases(self, tmp_path):
        from simple_tensorflow_tpu.debug.cli import CommandError

        cli = self._make_dump(tmp_path)
        assert cli.run_command("list_tensors") == cli.run_command("lt")
        import pytest as _pytest
        with _pytest.raises(CommandError, match="unknown command"):
            cli.run_command("wat")
        with _pytest.raises(CommandError, match="not dumped"):
            cli.run_command("pt nope:0")
        assert "commands" in cli.run_command("help")

    def test_interactive_loop(self, tmp_path):
        import io

        cli = self._make_dump(tmp_path)
        out = io.StringIO()
        cli.interactive(stdin=io.StringIO("runs\nbadcmd\nexit\n"),
                        stdout=out)
        s = out.getvalue()
        assert "run_1" in s and "error:" in s


class TestDebugSinks:
    """URL debug sinks (ref: core/debug/
    debug_io_utils.h, debug_service.proto): watched tensors stream to
    file:// dirs and tcp:// readers in other processes."""

    def _run_watched(self, debug_urls, tmp_path):
        import numpy as np

        import simple_tensorflow_tpu as stf
        from simple_tensorflow_tpu import debug as stf_debug

        stf.reset_default_graph()
        x = stf.placeholder(stf.float32, [4], name="dbg_x")
        y = stf.multiply(x, 2.0, name="dbg_y")
        sess = stf.Session()
        wrapped = stf_debug.DumpingDebugWrapperSession(
            sess, str(tmp_path / "dumps"), debug_urls=debug_urls)
        xv = np.arange(4, dtype=np.float32)
        out = wrapped.run(y, feed_dict={x: xv})
        wrapped.close()
        return xv, np.asarray(out)

    def test_file_url_sink(self, tmp_path):
        import json as _json

        import numpy as np

        sink_dir = tmp_path / "sinkdir"
        xv, out = self._run_watched([f"file://{sink_dir}"], tmp_path)
        np.testing.assert_allclose(out, xv * 2.0)
        man = _json.loads((sink_dir / "run_1" / "manifest.json")
                          .read_text())
        assert "dbg_y:0" in man["tensors"]
        got = np.load(sink_dir / "run_1" /
                      man["tensors"]["dbg_y:0"]["file"])
        np.testing.assert_allclose(got, xv * 2.0)

    def test_tcp_sink_to_in_process_listener(self, tmp_path):
        import numpy as np

        from simple_tensorflow_tpu.debug import io_utils

        listener = io_utils.DebugListener()
        try:
            xv, _ = self._run_watched(
                [f"tcp://127.0.0.1:{listener.port}"], tmp_path)
            listener.wait(timeout=30)
            names = {h["name"] for h, _ in listener.events}
            assert "dbg_y:0" in names, names
            for h, arr in listener.events:
                if h["name"] == "dbg_y:0":
                    np.testing.assert_allclose(arr, xv * 2.0)
        finally:
            listener.close()

    def test_tcp_sink_to_reader_subprocess(self, tmp_path):
        """The cross-process contract: a reader SUBPROCESS receives the
        streamed tensors (ref debug_gateway / grpc_debug_server)."""
        import json as _json
        import socket as _socket
        import subprocess
        import sys

        import numpy as np

        with _socket.socket() as s:
            s.bind(("127.0.0.1", 0))
            port = s.getsockname()[1]
        env = dict(os.environ)
        env["JAX_PLATFORMS"] = "cpu"
        repo_root = os.path.dirname(os.path.dirname(
            os.path.abspath(__file__)))
        env["PYTHONPATH"] = repo_root + os.pathsep + env.get(
            "PYTHONPATH", "")
        out_dir = str(tmp_path / "received")
        proc = subprocess.Popen(
            [sys.executable, "-m", "simple_tensorflow_tpu.debug.io_utils",
             "--listen", str(port), "--out", out_dir],
            stdout=subprocess.PIPE, text=True, env=env)
        try:
            # wait for the listener to come up
            line = proc.stdout.readline()
            assert "listening" in line, line
            xv, _ = self._run_watched([f"tcp://127.0.0.1:{port}"],
                                      tmp_path)
            out_text, _ = proc.communicate(timeout=60)
            lines = [_json.loads(l) for l in out_text.splitlines() if l]
            assert lines[-1].get("done", 0) >= 1, lines
            by_name = {d["name"]: d for d in lines if "name" in d}
            assert "dbg_y:0" in by_name
            got = np.load(os.path.join(out_dir, "run1_dbg_y_0.npy"))
            np.testing.assert_allclose(got, xv * 2.0)
        finally:
            if proc.poll() is None:
                proc.kill()

    def test_bad_url_raises(self):
        from simple_tensorflow_tpu.debug import io_utils

        with pytest.raises(ValueError, match="unsupported debug URL"):
            io_utils.sink_for_url("ftp://nope:1")


class TestAotCompileCLI:
    """tfcompile-equivalent CLI (ref:
    compiler/aot/compile.cc): frozen GraphDef-JSON -> self-contained
    serialized executable + manifest + servable SavedModel twin."""

    def _write_frozen_graph(self, tmp_path):
        import simple_tensorflow_tpu as stf

        stf.reset_default_graph()
        x = stf.placeholder(stf.float32, [3, 4], name="aot_x")
        w = stf.constant(
            np.arange(12, dtype=np.float32).reshape(4, 3) * 0.1,
            name="aot_w")
        y = stf.tanh(stf.matmul(x, w), name="aot_y")
        from simple_tensorflow_tpu.framework import graph_io

        gd = graph_io.graph_to_graphdef(stf.get_default_graph())
        path = str(tmp_path / "g.json")
        with open(path, "w") as f:
            json.dump(gd, f)
        xv = np.random.RandomState(0).randn(3, 4).astype(np.float32)
        expected = stf.Session().run(y, {x: xv})
        return path, xv, np.asarray(expected)

    def test_cli_compile_load_run(self, tmp_path):
        import subprocess
        import sys

        graph_path, xv, expected = self._write_frozen_graph(tmp_path)
        out_dir = str(tmp_path / "prog")
        env = dict(os.environ)
        env["JAX_PLATFORMS"] = "cpu"
        repo_root = os.path.dirname(os.path.dirname(
            os.path.abspath(__file__)))
        env["PYTHONPATH"] = repo_root + os.pathsep + env.get(
            "PYTHONPATH", "")
        proc = subprocess.run(
            [sys.executable, "-m",
             "simple_tensorflow_tpu.tools.aot_compile",
             "--graph", graph_path, "--feed", "aot_x:0",
             "--fetch", "aot_y:0", "--out", out_dir],
            capture_output=True, text=True, timeout=300, env=env)
        assert proc.returncode == 0, proc.stderr[-2000:]
        summary = json.loads(proc.stdout.strip().splitlines()[-1])
        assert summary["n_fetches"] == 1

        # artifact layout
        assert os.path.exists(os.path.join(out_dir, "program.stablehlo"))
        manifest = json.load(open(os.path.join(out_dir, "manifest.json")))
        assert manifest["format"] == "stf-aot-v1"
        assert manifest["feeds"][0]["shape"] == [3, 4]
        assert os.path.isdir(os.path.join(out_dir, "saved_model"))

        # load + run the serialized program
        from simple_tensorflow_tpu import tools

        prog = tools.load_aot_program(out_dir)
        (got,) = prog(xv)
        np.testing.assert_allclose(np.asarray(got), expected, rtol=1e-5)

    def test_artifact_serves_through_savedmodel(self, tmp_path):
        """The saved_model twin loads through the ordinary loader (the
        same path StfSessionLoad drives from C)."""
        import simple_tensorflow_tpu as stf
        from simple_tensorflow_tpu import saved_model as sm
        from simple_tensorflow_tpu import tools

        graph_path, xv, expected = self._write_frozen_graph(tmp_path)
        out_dir = str(tmp_path / "prog2")
        with open(graph_path) as f:
            tools.aot_compile(f.read(), ["aot_x:0"], ["aot_y:0"], out_dir)
        stf.reset_default_graph()
        sess = stf.Session()
        sm.load(sess, [sm.tag_constants.SERVING],
                os.path.join(out_dir, "saved_model"))
        g = sess.graph
        got = sess.run(
            g.as_graph_element("aot_y:0", True, False),
            {g.as_graph_element("aot_x:0", True, False): xv})
        np.testing.assert_allclose(np.asarray(got), expected, rtol=1e-5)

    def test_stateful_graph_rejected(self, tmp_path):
        import simple_tensorflow_tpu as stf
        from simple_tensorflow_tpu import tools
        from simple_tensorflow_tpu.framework import graph_io

        stf.reset_default_graph()
        x = stf.placeholder(stf.float32, [2], name="sx")
        v = stf.Variable(np.ones(2, np.float32), name="sv")
        y = stf.add(x, v._ref, name="sy")
        gd = json.dumps(graph_io.graph_to_graphdef(
            stf.get_default_graph()))
        with pytest.raises(ValueError, match="stateful"):
            tools.aot_compile(gd, ["sx:0"], ["sy:0"],
                              str(tmp_path / "bad"))


class TestSelectiveRegistrationHeader:
    def test_header_lists_graph_ops(self):
        from simple_tensorflow_tpu import tools

        gd = {"node": [
            {"name": "a", "op": "Const", "attr": {}},
            {"name": "b", "op": "MatMul", "attr": {}},
            {"name": "c", "op": "Relu", "attr": {}},
        ]}
        ops = tools.required_ops([gd])
        assert ops == ["Const", "MatMul", "Relu"]
        header = tools.header_for_graphs([gd])
        assert '"MatMul",' in header
        # graph ops + the always-registered defaults (NoOp/_Recv/_Send)
        assert "kNumNecessaryOps = 6" in header
        assert '"NoOp",' in header
        assert "SHOULD_REGISTER_OP" in header

    def test_warns_on_unregistered(self):
        from simple_tensorflow_tpu import tools

        header = tools.header_for_graphs(
            [{"node": [{"name": "z", "op": "NotARealOp", "attr": {}}]}])
        assert "WARNING" in header and "NotARealOp" in header

    def test_cli(self, tmp_path):
        import subprocess
        import sys

        gd = {"node": [{"name": "a", "op": "Const", "attr": {}}]}
        p = tmp_path / "g.json"
        p.write_text(json.dumps(gd))
        env = dict(os.environ)
        env["JAX_PLATFORMS"] = "cpu"
        repo_root = os.path.dirname(os.path.dirname(
            os.path.abspath(__file__)))
        env["PYTHONPATH"] = repo_root + os.pathsep + env.get(
            "PYTHONPATH", "")
        proc = subprocess.run(
            [sys.executable, "-m",
             "simple_tensorflow_tpu.tools."
             "print_selective_registration_header",
             "--graphs", str(p)],
            capture_output=True, text=True, timeout=120, env=env)
        assert proc.returncode == 0, proc.stderr[-1500:]
        assert '"Const",' in proc.stdout
