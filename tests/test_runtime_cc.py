"""Native C++ runtime tests via ctypes round-trips (SURVEY §4)."""

import json
import os
import struct

import numpy as np
import pytest

import simple_tensorflow_tpu as stf
from simple_tensorflow_tpu.lib import crc32c as pycrc
from simple_tensorflow_tpu.runtime import native

pytestmark = pytest.mark.skipif(not native.available(),
                                reason="native runtime not built")


def test_version():
    assert native.version().startswith("stf-runtime")


def test_crc32c_matches_python():
    for payload in [b"", b"a", b"hello world", os.urandom(1024),
                    os.urandom(7)]:
        # pure-python reference (force the table path with crc=0 short-circuit
        # bypassed by computing manually)
        crc = 0xFFFFFFFF
        for b in payload:
            crc = pycrc._TABLE[(crc ^ b) & 0xFF] ^ (crc >> 8)
        expect = crc ^ 0xFFFFFFFF
        assert native.crc32c(payload) == expect
        mask = (((expect >> 15) | (expect << 17)) + 0xA282EAD8) & 0xFFFFFFFF
        assert native.masked_crc32c(payload) == mask


def test_tfrecord_native_roundtrip(tmp_path):
    path = str(tmp_path / "native.tfrecord")
    records = [os.urandom(np.random.RandomState(i).randint(0, 2000))
               for i in range(50)] + [b""]
    native.write_tfrecords(path, records)
    got = list(native.read_tfrecords(path, batch=7))
    assert got == records


def test_tfrecord_native_vs_python_format(tmp_path):
    """Native writer output must parse with the pure-python reader and
    vice versa (format parity with ref record_writer.cc)."""
    from simple_tensorflow_tpu.lib.io import tf_record

    path = str(tmp_path / "a.tfrecord")
    records = [b"alpha", b"", b"x" * 1000]
    native.write_tfrecords(path, records)
    assert list(tf_record._read_records_py(path)) == records

    path2 = str(tmp_path / "b.tfrecord")
    with tf_record.TFRecordWriter(path2) as w:
        for r in records:
            w.write(r)
    assert list(native.read_tfrecords(path2)) == records


def test_tfrecord_gzip(tmp_path):
    path = str(tmp_path / "c.tfrecord.gz")
    records = [b"compressed", b"records" * 100]
    native.write_tfrecords(path, records, compression=2)
    # gzip magic
    with open(path, "rb") as f:
        assert f.read(2) == b"\x1f\x8b"
    assert list(native.read_tfrecords(path)) == records


def test_tfrecord_corruption_detected(tmp_path):
    path = str(tmp_path / "d.tfrecord")
    native.write_tfrecords(path, [b"payload-abcdef"])
    raw = bytearray(open(path, "rb").read())
    raw[14] ^= 0xFF  # flip a data byte
    open(path, "wb").write(bytes(raw))
    with pytest.raises(stf.errors.DataLossError):
        list(native.read_tfrecords(path))


def test_arena():
    a = native.Arena(block_bytes=4096)
    x = a.alloc_ndarray((16, 16), np.float32)
    x[:] = 3.0
    assert a.bytes_in_use >= 16 * 16 * 4
    y = a.alloc_ndarray((100000,), np.uint8)  # forces a new block
    y[:] = 7
    assert (x == 3.0).all()
    assert a.bytes_reserved >= a.bytes_in_use
    # 64-byte alignment
    assert x.ctypes.data % 64 == 0 and y.ctypes.data % 64 == 0
    a.reset()
    assert a.bytes_in_use == 0
    a.close()


def test_prune_toposort_flat():
    # diamond: 0->1, 0->2, 1->3, 2->3 ; extra orphan node 4
    edges = np.array([[0, 1], [0, 2], [1, 3], [2, 3]], np.int32)
    order = native.prune_toposort(5, edges, [3])
    assert order is not None and set(order) == {0, 1, 2, 3}
    pos = {n: i for i, n in enumerate(order)}
    assert pos[0] < pos[1] and pos[0] < pos[2]
    assert pos[1] < pos[3] and pos[2] < pos[3]
    # pruning: only ask for node 1
    order2 = native.prune_toposort(5, edges, [1])
    assert set(order2) == {0, 1}
    # cycle -> None
    cyc = np.array([[0, 1], [1, 0]], np.int32)
    assert native.prune_toposort(2, cyc, [1]) is None


def test_native_prune_matches_python_on_real_graph():
    from simple_tensorflow_tpu.framework import lowering

    stf.reset_default_graph()
    x = stf.placeholder(stf.float32, [4], name="x")
    h = x
    for i in range(600):  # push past _NATIVE_PRUNE_MIN_NODES
        h = h + float(i)
    loss = stf.reduce_sum(h)
    dead = stf.square(x)  # not an ancestor of loss
    g = stf.get_default_graph()
    assert len(g.get_operations()) >= lowering._NATIVE_PRUNE_MIN_NODES
    order = lowering.prune([loss.op], fed_tensors={x})
    names = {op.name for op in order}
    assert loss.op.name in names
    assert dead.op.name not in names
    # dependencies before dependents
    pos = {op: i for i, op in enumerate(order)}
    for op in order:
        for t in op.inputs:
            if t.op in pos:
                assert pos[t.op] < pos[op]


def test_cgraph_builds_importable_graphdef():
    g = native.CGraph()
    a = g.add_node("Const", "a")
    g.set_attr(a, "value_f", 2.0)
    g.add_output(a, "float32", [])
    b = g.add_node("Const", "b")
    g.set_attr(b, "value_f", 3.0)
    g.add_output(b, "float32", [])
    add = g.add_node("AddV2", "add")
    g.add_input(add, a, 0)
    g.add_input(add, b, 0)
    g.add_output(add, "float32", [])
    assert g.num_nodes == 3
    gd = json.loads(g.to_json())
    assert [n["name"] for n in gd["node"]] == ["a", "b", "add"]
    assert gd["node"][2]["input"] == ["a:0", "b:0"]
    assert gd["node"][0]["attr"]["value_f"] == 2.0
    assert gd["node"][2]["output_specs"] == [[[], "float32"]]
    g.close()


def test_cgraph_duplicate_name_raises():
    g = native.CGraph()
    g.add_node("NoOp", "n")
    with pytest.raises(stf.errors.OpError):
        g.add_node("NoOp", "n")
    g.close()


def test_session_run_uses_native_prune_smoke():
    """End-to-end: a big graph session step with the native pruner wired."""
    stf.reset_default_graph()
    x = stf.placeholder(stf.float32, [8], name="x")
    h = x
    for i in range(600):
        h = h * 1.0001 + 0.001
    y = stf.reduce_sum(h)
    with stf.Session() as sess:
        val = sess.run(y, {x: np.ones(8, np.float32)})
    assert np.isfinite(val)


def test_corruption_past_first_batch_no_duplicates(tmp_path):
    """Regression: a corrupt record past batch 1 must not restart the
    stream (previously the iterator fell back to the Python reader and
    re-delivered records 0..k twice)."""
    from simple_tensorflow_tpu.lib.io import tf_record

    path = str(tmp_path / "e.tfrecord")
    records = [struct.pack("<I", i) * 3 for i in range(300)]
    native.write_tfrecords(path, records)
    raw = bytearray(open(path, "rb").read())
    # corrupt a byte inside record ~290's payload: each record is
    # 12 + 12 + 4 = 28 bytes on disk
    raw[28 * 290 + 14] ^= 0xFF
    open(path, "wb").write(bytes(raw))
    got = []
    with pytest.raises(stf.errors.DataLossError):
        for r in tf_record.tf_record_iterator(path):
            got.append(r)
    # good prefix delivered exactly once, in order
    assert got == records[:290]


def test_run_from_c_savedmodel_roundtrip(tmp_path):
    """StfSessionRun equivalent (ref c/c_api.h TF_SessionRun): export an
    MNIST softmax forward as a SavedModel, load + run it through the C
    entry points via ctypes, and match an in-process Session.run."""
    from simple_tensorflow_tpu.runtime import native

    lib = native.load_session_lib()
    if lib is None:
        pytest.skip("libstf_session.so unavailable (no python3-config?)")

    import simple_tensorflow_tpu as stf
    from simple_tensorflow_tpu import saved_model as sm
    from simple_tensorflow_tpu.models import mnist

    stf.reset_default_graph()
    m = mnist.softmax_model(batch_size=None)
    rng = np.random.RandomState(0)
    X = rng.rand(4, 784).astype(np.float32)
    sess = stf.Session()
    sess.run(stf.global_variables_initializer())
    # non-trivial weights so the comparison means something
    sess.run(stf.assign(
        [v for v in stf.global_variables() if v.var_name == "W"][0],
        rng.randn(784, 10).astype(np.float32) * 0.1))
    expected = sess.run(m["logits"], {m["x"]: X})
    export_dir = str(tmp_path / "export")
    sm.simple_save(sess, export_dir, inputs={"x": m["x"]},
                   outputs={"logits": m["logits"]})

    c = __import__("ctypes")
    with native._Status(native._load()) as st:
        handle = lib.StfSessionLoad(export_dir.encode(), st.handle)
        st.check()
    assert handle

    dims = (c.c_int64 * 2)(4, 784)
    feed = (native.CTensorSpec * 1)()
    feed[0].dtype = b"float32"
    feed[0].rank = 2
    feed[0].dims = dims
    feed[0].data = X.ctypes.data_as(c.c_void_p)
    feed[0].nbytes = X.nbytes
    feed_names = (c.c_char_p * 1)(b"x")
    fetch_names = (c.c_char_p * 1)(b"logits")
    outs = (native.CTensorOut * 1)()
    with native._Status(native._load()) as st:
        lib.StfSessionRun(handle, feed_names, feed, 1,
                          fetch_names, 1, outs, st.handle)
        st.check()
    assert outs[0].dtype == b"float32"
    assert outs[0].rank == 2
    assert (outs[0].dims[0], outs[0].dims[1]) == (4, 10)
    got = np.ctypeslib.as_array(
        c.cast(outs[0].data, c.POINTER(c.c_float)), shape=(4, 10)).copy()
    lib.StfTensorOutRelease(c.byref(outs[0]))
    lib.StfSessionClose(handle)
    np.testing.assert_allclose(got, expected, rtol=1e-5)


def test_run_from_c_bad_fetch_sets_status(tmp_path):
    from simple_tensorflow_tpu.runtime import native

    lib = native.load_session_lib()
    if lib is None:
        pytest.skip("libstf_session.so unavailable")

    import simple_tensorflow_tpu as stf
    from simple_tensorflow_tpu import saved_model as sm
    from simple_tensorflow_tpu.models import mnist
    from simple_tensorflow_tpu.framework import errors

    stf.reset_default_graph()
    m = mnist.softmax_model(batch_size=None)
    sess = stf.Session()
    sess.run(stf.global_variables_initializer())
    export_dir = str(tmp_path / "export")
    sm.simple_save(sess, export_dir, inputs={"x": m["x"]},
                   outputs={"logits": m["logits"]})

    c = __import__("ctypes")
    with native._Status(native._load()) as st:
        handle = lib.StfSessionLoad(export_dir.encode(), st.handle)
        st.check()
    X = np.zeros((1, 784), np.float32)
    dims = (c.c_int64 * 2)(1, 784)
    feed = (native.CTensorSpec * 1)()
    feed[0].dtype = b"float32"
    feed[0].rank = 2
    feed[0].dims = dims
    feed[0].data = X.ctypes.data_as(c.c_void_p)
    feed[0].nbytes = X.nbytes
    feed_names = (c.c_char_p * 1)(b"x")
    fetch_names = (c.c_char_p * 1)(b"no_such_output")
    outs = (native.CTensorOut * 1)()
    with native._Status(native._load()) as st:
        lib.StfSessionRun(handle, feed_names, feed, 1,
                          fetch_names, 1, outs, st.handle)
        with pytest.raises(errors.InternalError, match="no_such_output"):
            st.check()
    lib.StfSessionClose(handle)


def test_arena_pool_staging_correctness():
    """ArenaPool: values survive the staging copy; buffers recycle after
    slots-1 further stages (the prefetch_to_device contract)."""
    from simple_tensorflow_tpu.runtime import native

    if not native.available():
        pytest.skip("native runtime unavailable")
    pool = native.ArenaPool(slots=3, block_bytes=1 << 16)
    rng = np.random.RandomState(0)
    batches = [rng.rand(8, 16).astype(np.float32) for _ in range(10)]
    staged = []
    for b in batches:
        s = pool.stage((b, {"lbl": b[:, 0].astype(np.int32)}))
        arr, d = s
        np.testing.assert_array_equal(arr, b)
        np.testing.assert_array_equal(d["lbl"], b[:, 0].astype(np.int32))
        # alignment contract for DMA staging
        assert arr.ctypes.data % 64 == 0
        staged.append(s)
    pool.close()


def test_prefetch_to_device_arena_staging():
    from simple_tensorflow_tpu.runtime import native
    from simple_tensorflow_tpu import data as stf_data

    if not native.available():
        pytest.skip("native runtime unavailable")
    rng = np.random.RandomState(1)
    X = rng.rand(32, 4).astype(np.float32)
    ds = stf_data.Dataset.from_tensor_slices(X).batch(8)
    out = list(ds.prefetch_to_device(buffer_size=2, arena_staging=True))
    assert len(out) == 4
    np.testing.assert_allclose(np.concatenate([np.asarray(o) for o in out]),
                               X)


def test_arena_pool_recycle_blocks_on_inflight():
    """A slot recycles only after its recorded in-flight arrays are ready
    (block_until_ready barrier), and staged values survive recycling when
    the transfer COPIES (TPU semantics — simulated with an explicit copy;
    CPU device_put aliases, which is why prefetch_to_device refuses arena
    staging there)."""
    from simple_tensorflow_tpu.runtime import native

    if not native.available():
        pytest.skip("native runtime unavailable")
    import jax
    import jax.numpy as jnp

    pool = native.ArenaPool(slots=2, block_bytes=1 << 16)
    rng = np.random.RandomState(2)
    batches = [rng.rand(4, 8).astype(np.float32) for _ in range(8)]
    devices = []
    for b in batches:
        staged = pool.stage(b)
        d = jnp.array(staged)  # explicit copy = TPU transfer semantics
        pool.mark_in_flight(d)
        devices.append(d)
    # every slot's inflight record was consumed by the recycle barrier
    # except the most recent ones still pending
    assert sum(x is not None for x in pool._inflight) <= 2
    for b, d in zip(batches, devices):
        np.testing.assert_array_equal(np.asarray(d), b)
    pool.close()


def test_prefetch_to_device_refuses_arena_on_cpu():
    """Explicit arena_staging=True on the CPU backend must fall back
    (device_put aliases aligned host buffers there) and stay correct far
    past the recycle window."""
    from simple_tensorflow_tpu.runtime import native
    from simple_tensorflow_tpu import data as stf_data

    if not native.available():
        pytest.skip("native runtime unavailable")
    rng = np.random.RandomState(3)
    X = rng.rand(80, 4).astype(np.float32)
    ds = stf_data.Dataset.from_tensor_slices(X).batch(8)
    out = list(ds.prefetch_to_device(buffer_size=2, arena_staging=True))
    assert len(out) == 10  # 10 batches >> buffer_size+2 slots
    np.testing.assert_allclose(
        np.concatenate([np.asarray(o) for o in out]), X)


def test_c_client_builds_grads_and_trains(tmp_path):
    """C++ client parity (ref cc/framework/scope.h,
    cc/framework/gradients.h:34, cc/framework/gradient_checker.cc):
    compile runtime_cc/client_demo.c — a pure-C program that builds
    y = xW + b, requests dL/dW via StfAddGradients, appends SGD ops,
    runs a train step through StfSessionFromGraphJson, and
    gradient-checks dL/dx against central differences — then match its
    numbers against the same model built natively in Python."""
    import shutil
    import subprocess

    cc_dir = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "runtime_cc")
    if not os.path.exists(os.path.join(cc_dir, "libstf_session.so")):
        if native.load_session_lib() is None:
            pytest.skip("libstf_session.so unavailable")
    gcc = shutil.which("gcc") or shutil.which("cc")
    if gcc is None:
        pytest.skip("no C compiler")

    exe = str(tmp_path / "client_demo")
    subprocess.run(
        [gcc, "-O1", "-o", exe,
         os.path.join(cc_dir, "client_demo.c"),
         "-I", cc_dir, "-L", cc_dir, "-lstf_runtime", "-lstf_session",
         "-lm", f"-Wl,-rpath,{cc_dir}"],
        check=True, capture_output=True, timeout=120)

    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.dirname(cc_dir) + os.pathsep + \
        env.get("PYTHONPATH", "")
    env["JAX_PLATFORMS"] = "cpu"
    proc = subprocess.run([exe], env=env, capture_output=True, text=True,
                          timeout=600)
    assert proc.returncode == 0, (proc.stdout, proc.stderr)
    lines = dict(line.split(" ", 1) for line in
                 proc.stdout.strip().splitlines() if " " in line)
    assert "OK" in proc.stdout

    c_l0 = float(lines["l0"])
    c_l1 = float(lines["l1"])
    c_gradcheck = float(lines["gradcheck_max_err"])
    c_w_after = np.array([float(v) for v in lines["W_after"].split()],
                         np.float32).reshape(3, 2)
    assert c_l1 < c_l0
    assert c_gradcheck < 1e-3

    # ---- same model natively in Python: numbers must match -------------
    B, D_IN, D_OUT, LR = 4, 3, 2, 0.1
    xv = np.sin(0.7 * np.arange(B * D_IN, dtype=np.float32) + 0.3) \
        .reshape(B, D_IN).astype(np.float32)
    tv = np.cos(0.3 * np.arange(B * D_OUT, dtype=np.float32) - 0.2) \
        .reshape(B, D_OUT).astype(np.float32)
    w0 = (0.05 * np.arange(1, D_IN * D_OUT + 1, dtype=np.float32)) \
        .reshape(D_IN, D_OUT)

    stf.reset_default_graph()
    x = stf.placeholder(stf.float32, [B, D_IN], name="x")
    t = stf.placeholder(stf.float32, [B, D_OUT], name="t")
    W = stf.Variable(w0, name="W")
    b = stf.Variable(np.zeros((D_OUT,), np.float32), name="b")
    y = stf.matmul(x, W._ref) + b._ref
    loss = stf.reduce_mean(stf.square(y - t))
    train = stf.train.GradientDescentOptimizer(LR).minimize(loss)
    sess = stf.Session()
    sess.run(stf.global_variables_initializer())
    feed = {x: xv, t: tv}
    py_l0 = sess.run(loss, feed)
    sess.run(train, feed)
    py_l1 = sess.run(loss, feed)
    py_w = np.asarray(sess.run(W.value()))

    np.testing.assert_allclose(c_l0, py_l0, rtol=1e-5)
    np.testing.assert_allclose(c_l1, py_l1, rtol=1e-5)
    np.testing.assert_allclose(c_w_after, py_w, rtol=1e-5, atol=1e-7)


# ---------------------------------------------------------------------------
# build rule (PR 21): the library is built from the sources on disk
# ---------------------------------------------------------------------------

def _scratch_cc_dir(tmp_path, monkeypatch):
    import shutil

    src = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "runtime_cc")
    dst = tmp_path / "runtime_cc"
    dst.mkdir()
    for f in os.listdir(src):
        if f.endswith((".cc", ".h", ".c")) or f == "Makefile":
            shutil.copy(os.path.join(src, f), dst / f)
    monkeypatch.setattr(native, "_CC_DIR", str(dst))
    return dst


def test_stale_library_is_rebuilt_from_sources(tmp_path, monkeypatch):
    import ctypes

    cc = _scratch_cc_dir(tmp_path, monkeypatch)
    # a "library" older than every source: not a shared object at all,
    # so loading it instead of rebuilding would fail loudly
    stale = cc / "libstf_runtime.so"
    stale.write_bytes(b"stale")
    os.utime(stale, (1, 1))
    path = native._find_or_build()
    assert path == str(stale)
    assert ctypes.CDLL(path).StfVersion is not None
    built = os.path.getmtime(path)
    assert native._find_or_build() == path      # up to date: no-op
    assert os.path.getmtime(path) == built


def test_failed_build_raises_with_compiler_stderr(tmp_path, monkeypatch):
    cc = _scratch_cc_dir(tmp_path, monkeypatch)
    (cc / "arena.cc").write_text("this is not C++\n")
    with pytest.raises(RuntimeError) as ei:
        native._find_or_build()
    assert "arena.cc" in str(ei.value) and "error" in str(ei.value)
