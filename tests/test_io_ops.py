"""Reader ops: WholeFile/TextLine/TFRecord/FixedLength/Identity readers,
read_file/matching_files, maybe_batch, and the queue-runner-driven
TFRecord training loop (SURVEY §2.8, ref python/ops/io_ops.py)."""

import os

import numpy as np
import pytest

import simple_tensorflow_tpu as stf
from simple_tensorflow_tpu.lib import example as example_mod
from simple_tensorflow_tpu.lib.io import tf_record


@pytest.fixture(autouse=True)
def fresh_graph():
    stf.reset_default_graph()
    yield


def _run_queue_runners(sess, coord):
    threads = stf.train.start_queue_runners(sess, coord=coord)
    return threads


class TestFileOps:
    def test_read_file(self, tmp_path):
        p = tmp_path / "a.txt"
        p.write_bytes(b"hello stf")
        out = stf.read_file(str(p))
        with stf.Session() as sess:
            v = sess.run(out)
        assert bytes(v.item() if hasattr(v, "item") else v) == b"hello stf"

    def test_write_file(self, tmp_path):
        p = str(tmp_path / "sub" / "out.txt")
        op = stf.write_file(p, "written")
        with stf.Session() as sess:
            sess.run(op)
        assert open(p).read() == "written"

    def test_matching_files(self, tmp_path):
        for n in ("x1.dat", "x2.dat", "y.dat"):
            (tmp_path / n).write_text("")
        out = stf.matching_files(str(tmp_path / "x*.dat"))
        with stf.Session() as sess:
            v = sess.run(out)
        names = [os.path.basename(str(s)) for s in np.ravel(v)]
        assert names == ["x1.dat", "x2.dat"]


class TestReaders:
    def _file_queue(self, files):
        return stf.train.string_input_producer(
            [str(f) for f in files], shuffle=False, num_epochs=1)

    def test_whole_file_reader(self, tmp_path):
        f1, f2 = tmp_path / "1.bin", tmp_path / "2.bin"
        f1.write_bytes(b"one")
        f2.write_bytes(b"two")
        q = self._file_queue([f1, f2])
        reader = stf.WholeFileReader()
        key, value = reader.read(q)
        coord = stf.train.Coordinator()
        with stf.Session() as sess:
            _run_queue_runners(sess, coord)
            k1, v1 = sess.run([key, value])
            k2, v2 = sess.run([key, value])
            coord.request_stop()
        got = {str(k1): bytes(v1.item()), str(k2): bytes(v2.item())}
        assert got == {str(f1): b"one", str(f2): b"two"}

    def test_text_line_reader_skips_header(self, tmp_path):
        f = tmp_path / "t.csv"
        f.write_text("header\nrow1\nrow2\n")
        q = self._file_queue([f])
        reader = stf.TextLineReader(skip_header_lines=1)
        key, value = reader.read(q)
        coord = stf.train.Coordinator()
        with stf.Session() as sess:
            _run_queue_runners(sess, coord)
            vals = [str(sess.run(value).item()) for _ in range(2)]
            n = int(sess.run(reader.num_records_produced()))
            coord.request_stop()
        assert vals == ["row1", "row2"]
        assert n == 2

    def test_tfrecord_reader_and_reset(self, tmp_path):
        path = tmp_path / "r.tfrecord"
        with tf_record.TFRecordWriter(str(path)) as w:
            for i in range(3):
                w.write(np.int32([i]).tobytes())
        q = self._file_queue([path])
        reader = stf.TFRecordReader()
        key, value = reader.read(q)
        coord = stf.train.Coordinator()
        with stf.Session() as sess:
            _run_queue_runners(sess, coord)
            recs = [int(np.frombuffer(sess.run(value).item(), np.int32)[0])
                    for _ in range(3)]
            assert recs == [0, 1, 2]
            assert int(sess.run(reader.num_work_units_completed())) >= 0
            sess.run(reader.reset())
            assert int(sess.run(reader.num_records_produced())) == 0
            coord.request_stop()

    def test_fixed_length_record_reader(self, tmp_path):
        f = tmp_path / "f.bin"
        f.write_bytes(b"HD" + b"aaaabbbbcccc" + b"FT")
        q = self._file_queue([f])
        reader = stf.FixedLengthRecordReader(record_bytes=4, header_bytes=2,
                                             footer_bytes=2)
        key, value = reader.read(q)
        coord = stf.train.Coordinator()
        with stf.Session() as sess:
            _run_queue_runners(sess, coord)
            vals = [bytes(sess.run(value).item()) for _ in range(3)]
            coord.request_stop()
        assert vals == [b"aaaa", b"bbbb", b"cccc"]

    def test_identity_reader_read_up_to(self, tmp_path):
        q = stf.train.string_input_producer(["a", "b", "c"], shuffle=False,
                                            num_epochs=1)
        reader = stf.IdentityReader()
        keys, values = reader.read_up_to(q, 2)
        coord = stf.train.Coordinator()
        with stf.Session() as sess:
            _run_queue_runners(sess, coord)
            k, v = sess.run([keys, values])
            coord.request_stop()
        assert [str(x) for x in np.ravel(v)] == ["a", "b"]


class TestEndToEndTFRecordTraining:
    def test_queue_runner_tfrecord_training_loop(self, tmp_path):
        """Queue-runner-driven training loop
        reading TFRecords end-to-end (reader -> parse_example -> model)."""
        rng = np.random.RandomState(0)
        W_true = np.float32([[1.0], [2.0]])
        path = str(tmp_path / "train.tfrecord")
        with tf_record.TFRecordWriter(path) as w:
            for _ in range(64):
                xv = rng.rand(2).astype(np.float32)
                yv = float(xv @ W_true[:, 0])
                ex = example_mod.Example(example_mod.Features({
                    "x": example_mod.Feature(
                        float_list=example_mod.FloatList(xv.tolist())),
                    "y": example_mod.Feature(
                        float_list=example_mod.FloatList([yv])),
                }))
                w.write(ex.SerializeToString())

        fq = stf.train.string_input_producer([path], shuffle=False)
        reader = stf.TFRecordReader()
        _, serialized = reader.read(fq)
        feats = stf.parse_single_example(serialized, {
            "x": stf.FixedLenFeature([2], stf.float32),
            "y": stf.FixedLenFeature([1], stf.float32),
        })
        x, y = feats["x"], feats["y"]

        w_var = stf.Variable(stf.zeros([2, 1]), name="w_e2e")
        pred = stf.matmul(stf.reshape(x, [1, 2]), w_var)
        loss = stf.reduce_mean(stf.square(pred - stf.reshape(y, [1, 1])))
        train_op = stf.train.GradientDescentOptimizer(0.5).minimize(loss)

        coord = stf.train.Coordinator()
        with stf.Session() as sess:
            sess.run(stf.global_variables_initializer())
            _run_queue_runners(sess, coord)
            l0 = float(sess.run(loss))
            for _ in range(60):
                sess.run(train_op)
            l1 = float(sess.run(loss))
            w_fit = np.asarray(sess.run(w_var.value()))
            coord.request_stop()
        assert l1 < l0
        assert np.allclose(w_fit, W_true, atol=0.35), w_fit


class TestMaybeBatch:
    def test_maybe_batch_filters(self):
        counter = stf.Variable(stf.constant(0.0), name="mb_count")
        bump = stf.assign_add(counter, stf.constant(1.0))
        with stf.get_default_graph().control_dependencies([bump]):
            item = counter.read_value()
        keep = stf.greater(item, stf.constant(2.0))  # drop 1.0, 2.0
        batched = stf.train.maybe_batch([item], keep, batch_size=2)
        coord = stf.train.Coordinator()
        with stf.Session() as sess:
            sess.run(stf.global_variables_initializer())
            _run_queue_runners(sess, coord)
            out = np.ravel(sess.run(batched))
            coord.request_stop()
        assert out.tolist() == [3.0, 4.0]


class TestNativeExampleFastParse:
    """C++ batch Example parser (ref core/util/
    example_proto_fast_parsing.cc) must agree exactly with the Python wire
    parser and honor FixedLen defaults/errors."""

    def _examples(self, n=6):
        from simple_tensorflow_tpu.lib.example import make_example

        out = []
        for i in range(n):
            feats = {"x": (np.arange(4, dtype=np.float32) + i).tolist(),
                     "y": [int(i)]}
            if i != 3:  # example 3 lacks 'z' -> default must apply
                feats["z"] = [i * 10, i * 10 + 1]
            out.append(make_example(**feats).SerializeToString())
        return out

    def test_fast_path_matches_python_path(self):
        import simple_tensorflow_tpu.ops.parsing_ops as po
        from simple_tensorflow_tpu.runtime import native

        if not native.available():
            import pytest as _pytest
            _pytest.skip("native runtime not built")
        serialized = self._examples()
        feats = {"x": po.FixedLenFeature([4], stf.float32),
                 "y": po.FixedLenFeature([1], stf.int64),
                 "z": po.FixedLenFeature([2], stf.int64,
                                         default_value=[-7, -7])}
        fast = po._parse_examples_fast(serialized, feats)
        assert fast is not None, "fast path did not engage"
        # force the python path for comparison
        slow = {}
        from simple_tensorflow_tpu.lib import example as example_mod

        batch = [example_mod.Example.FromString(s) for s in serialized]
        for name, spec in feats.items():
            rows = []
            for ex in batch:
                f = ex.features.feature.get(name)
                if f is None:
                    rows.append(np.asarray(spec.default_value))
                elif spec.dtype == stf.float32:
                    rows.append(np.asarray(f.float_list.value, np.float32))
                else:
                    rows.append(np.asarray(f.int64_list.value, np.int64))
            slow[name] = np.stack(rows).reshape([len(batch)] + spec.shape)
        for name in feats:
            np.testing.assert_array_equal(fast[name], slow[name],
                                          err_msg=name)

    def test_fast_path_errors(self):
        import pytest as _pytest

        import simple_tensorflow_tpu.ops.parsing_ops as po
        from simple_tensorflow_tpu.runtime import native

        if not native.available():
            _pytest.skip("native runtime not built")
        serialized = self._examples()
        # missing without default raises with the example index
        with _pytest.raises(ValueError, match="missing"):
            po._parse_examples_fast(
                serialized, {"z": po.FixedLenFeature([2], stf.int64)})
        # wrong size -> InvalidArgumentError (canonical code mapping)
        with _pytest.raises(stf.errors.InvalidArgumentError,
                            match="values|expected"):
            po._parse_examples_fast(
                serialized, {"x": po.FixedLenFeature([3], stf.float32)})
        # declared-kind mismatch reads as MISSING (slow-path semantics):
        # default applies when present, missing-error otherwise
        got = po._parse_examples_fast(
            serialized, {"x": po.FixedLenFeature([4], stf.int64,
                                                 default_value=[0] * 4)})
        np.testing.assert_array_equal(got["x"][0], [0, 0, 0, 0])
        with _pytest.raises(ValueError, match="missing"):
            po._parse_examples_fast(
                serialized, {"x": po.FixedLenFeature([4], stf.int64)})
        # malformed proto
        with _pytest.raises(stf.errors.InvalidArgumentError,
                            match="malformed"):
            po._parse_examples_fast(
                [b"\x0a\xff\xff\xff\xff\xff"],
                {"x": po.FixedLenFeature([4], stf.float32)})
        # bad default length names the feature
        with _pytest.raises(ValueError, match="default_value"):
            po._parse_examples_fast(
                serialized, {"z": po.FixedLenFeature(
                    [2], stf.int64, default_value=[1, 2, 3])})
        # >64 features falls back to the slow path (returns None)
        many = {f"f{i}": po.FixedLenFeature([1], stf.int64,
                                            default_value=[0])
                for i in range(70)}
        assert po._parse_examples_fast(serialized, many) is None
        # string / VarLen specs decline the fast path (None, no crash)
        assert po._parse_examples_fast(
            serialized, {"s": po.FixedLenFeature([1], stf.string)}) is None
        assert po._parse_examples_fast(
            serialized, {"x": po.VarLenFeature(stf.float32)}) is None

    def test_graph_parse_example_uses_it(self):
        # end to end through the graph op (fast path engages silently)
        import simple_tensorflow_tpu.ops.parsing_ops as po

        stf.reset_default_graph()
        serialized = self._examples(4)
        ph = stf.placeholder(stf.string, [None], name="ser")
        parsed = stf.parse_example(
            ph, {"x": po.FixedLenFeature([4], stf.float32),
                 "y": po.FixedLenFeature([1], stf.int64)})
        total = stf.reduce_sum(parsed["x"])
        with stf.Session() as sess:
            xv, tv = sess.run(
                [parsed["x"], total],
                {ph: np.array(serialized, dtype=object)})
        assert xv.shape == (4, 4)
        np.testing.assert_allclose(xv[2], [2., 3., 4., 5.])
