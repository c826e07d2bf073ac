"""Saver round-trips (mirrors ref saver_test.py, SURVEY §4)."""

import os

import numpy as np
import pytest

import simple_tensorflow_tpu as stf


@pytest.fixture(autouse=True)
def fresh_graph():
    stf.reset_default_graph()
    yield


class TestSaver:
    def test_save_restore_roundtrip(self, tmp_path):
        v = stf.Variable(stf.constant([1.0, 2.0]), name="v")
        w = stf.Variable(stf.constant(3.0), name="w")
        saver = stf.train.Saver()
        with stf.Session() as sess:
            sess.run(stf.global_variables_initializer())
            path = saver.save(sess, str(tmp_path / "model"))
            sess.run(stf.assign(v, stf.constant([9.0, 9.0])))
            sess.run(stf.assign(w, stf.constant(9.0)))
            saver.restore(sess, path)
            assert sess.run(v.value()).tolist() == [1.0, 2.0]
            assert float(sess.run(w.value())) == 3.0

    def test_bfloat16_variable_roundtrip(self, tmp_path):
        # npz reads bfloat16 bytes back as void: restore must recover
        # the dtype the index recorded (bf16 serving weights, PR 21)
        v = stf.Variable(stf.cast(stf.constant([1.5, -2.25, 3.0]),
                                  stf.bfloat16), name="bv")
        saver = stf.train.Saver()
        with stf.Session() as sess:
            sess.run(stf.global_variables_initializer())
            path = saver.save(sess, str(tmp_path / "bf"))
        with stf.Session() as sess2:
            saver.restore(sess2, path)
            out = sess2.run(v.value())
            assert str(out.dtype) == "bfloat16"
            assert out.astype(np.float32).tolist() == [1.5, -2.25, 3.0]

    def test_restore_into_fresh_session(self, tmp_path):
        v = stf.Variable(stf.constant([5.0]), name="rv")
        saver = stf.train.Saver()
        with stf.Session() as sess:
            sess.run(stf.global_variables_initializer())
            path = saver.save(sess, str(tmp_path / "m"))
        with stf.Session() as sess2:
            saver.restore(sess2, path)  # no initializer needed
            assert sess2.run(v.value()).tolist() == [5.0]

    def test_global_step_suffix_and_latest(self, tmp_path):
        v = stf.Variable(stf.zeros([]), name="gs_v")
        gs = stf.train.get_or_create_global_step()
        saver = stf.train.Saver()
        with stf.Session() as sess:
            sess.run(stf.global_variables_initializer())
            p1 = saver.save(sess, str(tmp_path / "ck"), global_step=gs)
            sess.run(stf.assign_add(gs, stf.constant(5, stf.int64)))
            p2 = saver.save(sess, str(tmp_path / "ck"), global_step=gs)
        assert p1.endswith("-0") and p2.endswith("-5")
        assert stf.train.latest_checkpoint(str(tmp_path)) == p2

    def test_max_to_keep(self, tmp_path):
        stf.Variable(stf.zeros([]), name="k_v")
        saver = stf.train.Saver(max_to_keep=2)
        with stf.Session() as sess:
            sess.run(stf.global_variables_initializer())
            paths = [saver.save(sess, str(tmp_path / "ck"), global_step=i)
                     for i in range(4)]
        # first two deleted, last two kept
        assert not any(os.path.exists(p + ".stfckpt") or
                       os.path.exists(p) or
                       any(f.startswith(os.path.basename(p))
                           for f in os.listdir(tmp_path))
                       for p in paths[:1])
        assert stf.train.latest_checkpoint(str(tmp_path)) == paths[-1]

    def test_var_list_subset(self, tmp_path):
        a = stf.Variable(stf.constant(1.0), name="sub_a")
        b = stf.Variable(stf.constant(2.0), name="sub_b")
        saver = stf.train.Saver(var_list=[a])
        with stf.Session() as sess:
            sess.run(stf.global_variables_initializer())
            path = saver.save(sess, str(tmp_path / "s"))
            sess.run(stf.assign(a, stf.constant(7.0)))
            sess.run(stf.assign(b, stf.constant(7.0)))
            saver.restore(sess, path)
            assert float(sess.run(a.value())) == 1.0
            assert float(sess.run(b.value())) == 7.0  # untouched

    def test_name_remap(self, tmp_path):
        a = stf.Variable(stf.constant([4.0]), name="orig")
        saver = stf.train.Saver()
        with stf.Session() as sess:
            sess.run(stf.global_variables_initializer())
            path = saver.save(sess, str(tmp_path / "m"))
        stf.reset_default_graph()
        b = stf.Variable(stf.zeros([1]), name="renamed")
        restorer = stf.train.Saver(var_list={"orig": b})
        with stf.Session() as sess:
            restorer.restore(sess, path)
            assert sess.run(b.value()).tolist() == [4.0]


class TestCheckpointUtils:
    def test_list_variables_and_load(self, tmp_path):
        stf.Variable(stf.constant([[1.0, 2.0]]), name="lv")
        saver = stf.train.Saver()
        with stf.Session() as sess:
            sess.run(stf.global_variables_initializer())
            path = saver.save(sess, str(tmp_path / "m"))
        from simple_tensorflow_tpu.train import checkpoint_utils

        names = dict(checkpoint_utils.list_variables(path))
        assert "lv" in names and names["lv"] == [1, 2]
        reader = checkpoint_utils.load_checkpoint(path)
        np.testing.assert_allclose(reader.get_tensor("lv"), [[1.0, 2.0]])

    def test_init_from_checkpoint(self, tmp_path):
        stf.Variable(stf.constant([8.0]), name="src")
        saver = stf.train.Saver()
        with stf.Session() as sess:
            sess.run(stf.global_variables_initializer())
            path = saver.save(sess, str(tmp_path / "m"))
        stf.reset_default_graph()
        dst = stf.Variable(stf.zeros([1]), name="dst")
        from simple_tensorflow_tpu.train import checkpoint_utils

        checkpoint_utils.init_from_checkpoint(path, {"src": dst})
        with stf.Session() as sess:
            sess.run(stf.global_variables_initializer())
            assert sess.run(dst.value()).tolist() == [8.0]


class TestSaverWithOptimizerState:
    def test_slots_roundtrip(self, tmp_path):
        v = stf.Variable(stf.constant([1.0]), name="ov")
        loss = stf.reduce_sum(stf.square(v._ref))
        train = stf.train.AdamOptimizer(0.1).minimize(loss)
        saver = stf.train.Saver()
        with stf.Session() as sess:
            sess.run(stf.global_variables_initializer())
            for _ in range(3):
                sess.run(train)
            val3 = sess.run(v.value())
            path = saver.save(sess, str(tmp_path / "m"))
            for _ in range(2):
                sess.run(train)
            val5 = sess.run(v.value())
            saver.restore(sess, path)
            for _ in range(2):
                sess.run(train)
            val5_replay = sess.run(v.value())
        # deterministic replay incl. Adam m/v slots
        np.testing.assert_allclose(val5, val5_replay, rtol=1e-6)
        assert not np.allclose(val3, val5)


class TestOrbaxBackend:
    def test_sharded_roundtrip_preserves_sharding(self, tmp_path):
        """8-device mesh: save sharded variables via orbax, restore into a
        fresh session with the shardings intact — no host gather."""
        from simple_tensorflow_tpu import parallel

        mesh = parallel.Mesh({"tp": 8})
        with mesh:
            w = stf.Variable(stf.random_normal([16, 8], seed=3), name="ow")
            parallel.shard_variable(w, "tp", None)
            b = stf.Variable(stf.zeros([8]), name="ob")
            saver = stf.train.Saver(backend="orbax")
            with stf.Session() as sess:
                sess.run(stf.global_variables_initializer())
                w0 = np.asarray(sess.run(w.value()))
                arr = sess._variable_store.values["ow"]
                assert len(arr.sharding.device_set) == 8
                path = saver.save(sess, str(tmp_path / "om"))
            assert os.path.isdir(path + ".orbax")
            assert not os.path.exists(path + ".stfz")  # no npz host bundle
            with stf.Session() as sess2:
                saver.restore(sess2, path)
                arr2 = sess2._variable_store.values["ow"]
                # restored straight into the mesh sharding, not replicated
                assert len(arr2.sharding.device_set) == 8
                assert np.allclose(np.asarray(sess2.run(w.value())), w0)
        assert stf.train.latest_checkpoint(str(tmp_path)) == path

    def test_bad_backend_rejected(self):
        with pytest.raises(ValueError):
            stf.train.Saver(backend="protobuf")


class TestHostStateResume:
    def test_rng_stream_resumes_identically(self, tmp_path):
        """Dropout masks after restore must equal the masks the original
        run would have produced (SURVEY §5 RNG-key resume)."""
        x = stf.constant(np.ones((4, 64), np.float32))
        y = stf.nn.dropout(x, keep_prob=0.5)
        v = stf.Variable(stf.constant(1.0), name="hv")
        saver = stf.train.Saver()
        with stf.Session() as sess:
            sess.run(stf.global_variables_initializer())
            sess.run(y)  # advance the RNG stream
            path = saver.save(sess, str(tmp_path / "h"))
            expected = [np.asarray(sess.run(y)) for _ in range(3)]
        with stf.Session() as sess2:
            saver.restore(sess2, path)
            resumed = [np.asarray(sess2.run(y)) for _ in range(3)]
        for a, b in zip(expected, resumed):
            assert np.array_equal(a, b)

    def test_iterator_position_resumes(self, tmp_path):
        from simple_tensorflow_tpu import data as stf_data

        ds = stf_data.Dataset.from_tensor_slices(
            np.arange(10, dtype=np.int32)).repeat()
        it = ds.make_one_shot_iterator()
        nxt = it.get_next()
        v = stf.Variable(stf.constant(0.0), name="iv")
        saver = stf.train.Saver()
        with stf.Session() as sess:
            sess.run(stf.global_variables_initializer())
            seen = [int(sess.run(nxt)) for _ in range(4)]
            assert seen == [0, 1, 2, 3]
            path = saver.save(sess, str(tmp_path / "it"))
            assert int(sess.run(nxt)) == 4
        with stf.Session() as sess2:
            saver.restore(sess2, path)
            assert int(sess2.run(nxt)) == 4  # resumes where save happened


class TestAtomicCheckpointWrites:
    """ISSUE 10 satellite: the .stfz/.index.json writers and
    update_checkpoint_state commit through temp+fsync+os.replace with a
    content checksum in the index."""

    def test_index_carries_checksum_and_sharding_fields(self, tmp_path):
        stf.Variable(stf.constant([1.0]), name="at_v")
        saver = stf.train.Saver()
        with stf.Session() as sess:
            sess.run(stf.global_variables_initializer())
            path = saver.save(sess, str(tmp_path / "m"))
        import json

        doc = json.load(open(path + ".index.json"))
        assert doc["version"] >= 2
        assert doc["checksum"].startswith("sha256:")
        assert doc["data_bytes"] == os.path.getsize(path + ".stfz")
        assert "sharding" in doc["tensors"]["at_v"]
        from simple_tensorflow_tpu.checkpoint import atomic

        assert atomic.checksum_file(path + ".stfz") == doc["checksum"]

    def test_interrupted_state_update_keeps_previous_pointer(
            self, tmp_path):
        from simple_tensorflow_tpu.checkpoint import atomic

        stf.Variable(stf.constant([1.0]), name="sp_v")
        saver = stf.train.Saver()
        with stf.Session() as sess:
            sess.run(stf.global_variables_initializer())
            p1 = saver.save(sess, str(tmp_path / "ck"), global_step=1)

            def boom(point):
                if point == "state:synced_tmp":
                    raise OSError("yanked mid-commit")

            atomic.set_fault_hook(boom)
            try:
                with pytest.raises(OSError):
                    saver.save(sess, str(tmp_path / "ck"), global_step=2)
            finally:
                atomic.set_fault_hook(None)
        # the step-2 bundle is on disk, but the pointer never moved —
        # and it still parses (no truncated JSON)
        assert stf.train.latest_checkpoint(str(tmp_path)) == p1
        assert stf.train.get_checkpoint_state(str(tmp_path)) is not None

    def test_restore_rejects_corrupted_bundle(self, tmp_path):
        v = stf.Variable(stf.constant([3.0]), name="cr_v")
        saver = stf.train.Saver()
        with stf.Session() as sess:
            sess.run(stf.global_variables_initializer())
            path = saver.save(sess, str(tmp_path / "m"))
        with open(path + ".stfz", "r+b") as f:
            f.seek(20)
            b = f.read(1)
            f.seek(20)
            f.write(bytes([b[0] ^ 0xFF]))
        with stf.Session() as sess2:
            with pytest.raises(stf.errors.DataLossError):
                saver.restore(sess2, path)


class TestKeepEveryNHours:
    def test_keep_forever_based_on_checkpoint_time(self, tmp_path, monkeypatch):
        """ref semantics: a checkpoint whose save time crosses the keep
        interval is kept forever when evicted; others are deleted."""
        import simple_tensorflow_tpu.train.saver as saver_mod

        t = [1000.0]
        monkeypatch.setattr(saver_mod.time, "time", lambda: t[0])
        v = stf.Variable(stf.constant(1.0), name="kv")
        saver = stf.train.Saver(max_to_keep=1,
                                keep_checkpoint_every_n_hours=1.0)
        with stf.Session() as sess:
            sess.run(stf.global_variables_initializer())
            p1 = saver.save(sess, str(tmp_path / "ck"), global_step=1)
            t[0] += 1800.0  # p1 evicted next save: 1000 < 4600 -> delete
            p2 = saver.save(sess, str(tmp_path / "ck"), global_step=2)
            t[0] += 3600.0  # p2 evicted next save: 2800 < 4600 -> delete
            p3 = saver.save(sess, str(tmp_path / "ck"), global_step=3)
            t[0] += 600.0   # p3 evicted next save: 6400 > 4600 -> keep
            p4 = saver.save(sess, str(tmp_path / "ck"), global_step=4)
        assert not stf.train.checkpoint_exists(p1)  # deleted
        assert not stf.train.checkpoint_exists(p2)  # deleted
        assert stf.train.checkpoint_exists(p3)      # kept forever
        assert stf.train.checkpoint_exists(p4)      # newest
