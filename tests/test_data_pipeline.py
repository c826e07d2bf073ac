"""Parallel input-pipeline engine tests (ISSUE 5): stage-graph executor,
sharded C++ TFRecord reads, batch Example parsing, AUTOTUNE, and the
determinism/checkpoint contracts of docs/DATA.md."""

import os
import threading
import time

import numpy as np
import pytest

import simple_tensorflow_tpu as stf
from simple_tensorflow_tpu import data as stf_data
from simple_tensorflow_tpu.data import AUTOTUNE
from simple_tensorflow_tpu.lib.example import make_example
from simple_tensorflow_tpu.lib.io import tf_record
from simple_tensorflow_tpu.ops import parsing_ops as po
from simple_tensorflow_tpu.platform import monitoring


@pytest.fixture(autouse=True)
def fresh_graph():
    stf.reset_default_graph()
    yield


def _write_shards(tmp_path, n_shards=4, n_records=20, prefix="s"):
    files = []
    for s in range(n_shards):
        p = str(tmp_path / f"{prefix}{s}.tfrecord")
        with tf_record.TFRecordWriter(p) as w:
            for i in range(n_records):
                w.write(make_example(
                    x=[float(s * 1000 + i), float(i) + 0.5],
                    y=[s * 1000 + i]).SerializeToString())
        files.append(p)
    return files


class TestPrefetchErrorPropagation:
    def test_source_error_not_swallowed(self):
        """Regression (satellite 1): the seed's prefetch worker wrapped
        the source loop in ``finally: q.put(DONE)`` — any source error
        became silent end-of-data."""
        def bad():
            yield np.int32(1)
            yield np.int32(2)
            raise ValueError("source exploded")

        ds = stf_data.Dataset.from_generator(bad).prefetch(2)
        got = []
        with pytest.raises(ValueError, match="source exploded"):
            for x in ds:
                got.append(int(x))
        assert got == [1, 2]  # elements before the error still arrive

    def test_parallel_map_delivers_inflight_before_source_error(self):
        """A SOURCE error behind a parallel map must not drop mapped
        elements already in flight — sequential delivers all produced
        elements then the error; parallel must match (at-position
        contract, docs/DATA.md)."""
        def src():
            for i in range(20):
                yield np.int64(i)
            raise RuntimeError("tail corrupt")

        for det in (True, False):
            ds = stf_data.Dataset.from_generator(src).map(
                lambda x: x * 2, num_parallel_calls=4, deterministic=det)
            got = []
            with pytest.raises(RuntimeError, match="tail corrupt"):
                for x in ds:
                    got.append(int(x))
            assert sorted(got) == [2 * i for i in range(20)]
            if det:
                assert got == [2 * i for i in range(20)]

    def test_explicit_prefetch_capacity_honored(self):
        """prefetch(64) must build a 64-slot ring — the 16 cap bounds
        only AUTOTUNE growth (regression: fixed sizes were clamped)."""
        list(stf_data.Dataset.from_tensor_slices(
            np.arange(5)).map(lambda x: x, num_parallel_calls=2)
            .prefetch(64))
        cells = monitoring.get_metric(
            "/stf/data/parallelism").snapshot()["cells"]
        assert cells["prefetch:0"] == 64

    def test_map_func_error_positioned(self):
        def boom(x):
            if int(x) == 5:
                raise RuntimeError("bad element")
            return x * 2

        ds = stf_data.Dataset.from_tensor_slices(
            np.arange(10)).map(boom, num_parallel_calls=3)
        got = []
        with pytest.raises(RuntimeError, match="bad element"):
            for x in ds:
                got.append(int(x))
        # ordered mode: every element before the failing one was emitted
        assert got == [0, 2, 4, 6, 8]


class TestTFRecordDatasetOptions:
    def test_unsupported_compression_raises(self, tmp_path):
        p = str(tmp_path / "x.tfrecord")
        with tf_record.TFRecordWriter(p) as w:
            w.write(b"r")
        with pytest.raises(stf.errors.UnimplementedError,
                           match="compression_type"):
            stf_data.TFRecordDataset(p, compression_type="ZLIB")

    def test_gzip_compression_supported(self, tmp_path):
        p = str(tmp_path / "g.tfrecord.gz")
        opts = tf_record.TFRecordOptions(
            tf_record.TFRecordCompressionType.GZIP)
        with tf_record.TFRecordWriter(p, opts) as w:
            for i in range(7):
                w.write(f"z{i}".encode())
        got = list(stf_data.TFRecordDataset(p, compression_type="GZIP"))
        assert got == [f"z{i}".encode() for i in range(7)]

    def test_buffer_size_honored(self, tmp_path):
        files = _write_shards(tmp_path, n_shards=2, n_records=10)
        base = list(stf_data.TFRecordDataset(files))
        small = list(stf_data.TFRecordDataset(files, buffer_size=4096))
        assert small == base
        with pytest.raises(ValueError, match="buffer_size"):
            stf_data.TFRecordDataset(files, buffer_size=0)

    def test_bad_parallel_arg(self, tmp_path):
        files = _write_shards(tmp_path, n_shards=1, n_records=1)
        with pytest.raises(ValueError, match="num_parallel_reads"):
            stf_data.TFRecordDataset(files, num_parallel_reads=-3)


class TestShardedReadDeterminism:
    def test_parallel_reads_match_sequential_stream(self, tmp_path):
        files = _write_shards(tmp_path, n_shards=6, n_records=15)
        seq = list(stf_data.TFRecordDataset(files))
        for n in (2, 4, AUTOTUNE):
            par = list(stf_data.TFRecordDataset(files,
                                                num_parallel_reads=n))
            assert par == seq  # byte-identical, strict shard order

    def test_full_chain_determinism(self, tmp_path):
        """Ordered map + seeded shuffle + parallel reads + prefetch
        reproduce the sequential chain's element stream exactly
        (acceptance criterion)."""
        files = _write_shards(tmp_path, n_shards=4, n_records=16)
        spec = {"x": po.FixedLenFeature([2], stf.float32),
                "y": po.FixedLenFeature([1], stf.int64)}

        def chain(parallel):
            ds = stf_data.TFRecordDataset(
                files,
                num_parallel_reads=(AUTOTUNE if parallel else None))
            ds = ds.shuffle(8, seed=42)
            ds = ds.batch(4).parse_example(spec)
            ds = ds.map(lambda d: {"x": d["x"] * 2.0, "y": d["y"]},
                        num_parallel_calls=(4 if parallel else None))
            if parallel:
                ds = ds.prefetch(AUTOTUNE)
            return list(ds)

        seq, par = chain(False), chain(True)
        assert len(seq) == len(par) == 16
        for a, b in zip(seq, par):
            np.testing.assert_array_equal(a["x"], b["x"])
            np.testing.assert_array_equal(a["y"], b["y"])

    def test_unordered_map_same_multiset(self):
        ds = stf_data.Dataset.from_tensor_slices(np.arange(40)).map(
            lambda x: x + 100, num_parallel_calls=4, deterministic=False)
        assert sorted(int(x) for x in ds) == [i + 100 for i in range(40)]


class TestInterleave:
    def test_cycle_semantics(self):
        ds = stf_data.Dataset.range(4).interleave(
            lambda x: stf_data.Dataset.from_tensor_slices(
                np.arange(int(x) * 10, int(x) * 10 + 3)),
            cycle_length=2, block_length=1)
        assert [int(v) for v in ds] == [
            0, 10, 1, 11, 2, 12, 20, 30, 21, 31, 22, 32]

    def test_parallel_matches_sequential(self, tmp_path):
        files = _write_shards(tmp_path, n_shards=5, n_records=9)

        def mk(n):
            return stf_data.Dataset.from_tensor_slices(
                np.array(files, dtype=object)).interleave(
                    lambda f: stf_data.TFRecordDataset(
                        f.decode() if isinstance(f, bytes) else str(f)),
                    cycle_length=3, block_length=2, num_parallel_calls=n)

        seq = list(mk(None))
        assert len(seq) == 45
        for n in (2, AUTOTUNE):
            assert list(mk(n)) == seq

    def test_bad_args(self):
        with pytest.raises(ValueError, match="cycle_length"):
            stf_data.Dataset.range(2).interleave(lambda x: None,
                                                 cycle_length=0)


class TestParseParity:
    """C++ batch parse vs pure-Python parse on golden TFRecord shards
    (satellite: parity gate for the one-C-call-per-batch parser)."""

    def _golden(self, tmp_path, n=13):
        p = str(tmp_path / "golden.tfrecord")
        rng = np.random.RandomState(0)
        rows = []
        with tf_record.TFRecordWriter(p) as w:
            for i in range(n):
                x = rng.randn(3).astype(np.float32)
                y = rng.randint(-5, 5, size=2)
                rows.append((x, y))
                w.write(make_example(x=list(map(float, x)),
                                     y=list(map(int, y)))
                        .SerializeToString())
        return p, rows

    def test_native_vs_python_parity(self, tmp_path, monkeypatch):
        from simple_tensorflow_tpu.runtime import native

        if not native.available():
            pytest.skip("native runtime not built")
        p, rows = self._golden(tmp_path)
        spec = {"x": po.FixedLenFeature([3], stf.float32),
                "y": po.FixedLenFeature([2], stf.int64)}
        serialized = list(tf_record.tf_record_iterator(p))
        fast = po.parse_example_py(serialized, spec)
        assert fast is not None
        monkeypatch.setattr(po, "_parse_examples_fast",
                            lambda *a, **k: None)
        slow = po.parse_example_py(serialized, spec)
        np.testing.assert_array_equal(fast["x"], slow["x"])
        np.testing.assert_array_equal(fast["y"], slow["y"])
        assert fast["x"].dtype == slow["x"].dtype == np.float32
        assert fast["y"].dtype == slow["y"].dtype == np.int64
        for i, (x, y) in enumerate(rows):
            np.testing.assert_allclose(fast["x"][i], x)
            np.testing.assert_array_equal(fast["y"][i], y)

    def test_defaults_and_missing_parity(self, tmp_path, monkeypatch):
        from simple_tensorflow_tpu.runtime import native

        if not native.available():
            pytest.skip("native runtime not built")
        serialized = [make_example(a=[1.0, 2.0]).SerializeToString(),
                      make_example(b=[7]).SerializeToString()]
        spec = {"a": po.FixedLenFeature([2], stf.float32,
                                        default_value=[0.5, 0.5]),
                "b": po.FixedLenFeature([1], stf.int64, default_value=9)}
        fast = po.parse_example_py(serialized, spec)
        monkeypatch.setattr(po, "_parse_examples_fast",
                            lambda *a, **k: None)
        slow = po.parse_example_py(serialized, spec)
        np.testing.assert_array_equal(fast["a"], slow["a"])
        np.testing.assert_array_equal(fast["b"], slow["b"])

    def test_parse_path_counters(self, tmp_path):
        before = monitoring.get_metric(
            "/stf/data/parse_example_batches").snapshot()["cells"]
        serialized = [make_example(v=[1.0]).SerializeToString()]
        po.parse_example_py(serialized,
                            {"v": po.FixedLenFeature([1], stf.float32)})
        after = monitoring.get_metric(
            "/stf/data/parse_example_batches").snapshot()["cells"]
        assert sum(after.values()) == sum(before.values()) + 1


class TestIteratorCheckpointParallel:
    def test_save_restore_mid_stream_with_parallel_stages(self, tmp_path):
        """Iterator position checkpoint/restore while sharded reads +
        parallel map + prefetch are active (satellite test matrix)."""
        files = _write_shards(tmp_path, n_shards=3, n_records=8)
        spec = {"x": po.FixedLenFeature([2], stf.float32),
                "y": po.FixedLenFeature([1], stf.int64)}

        def mk():
            return (stf_data.TFRecordDataset(files, num_parallel_reads=2)
                    .batch(4).parse_example(spec)
                    .map(lambda d: d["y"], num_parallel_calls=2)
                    .prefetch(2))

        ref = list(mk())
        it = stf_data.Iterator(mk())
        consumed = [it._next_value() for _ in range(2)]
        for got, want in zip(consumed, ref[:2]):
            np.testing.assert_array_equal(got, want)
        state = it.save_state()
        assert state == {"position": 2}
        it.close()  # abandoning the half-consumed stream leaks it

        it2 = stf_data.Iterator(mk())
        it2.restore_state(state)
        rest = []
        while True:
            try:
                rest.append(it2._next_value())
            except stf.errors.OutOfRangeError:
                break
        assert len(rest) == len(ref) - 2
        for got, want in zip(rest, ref[2:]):
            np.testing.assert_array_equal(got, want)

    def test_session_driven_get_next_parallel(self, tmp_path):
        files = _write_shards(tmp_path, n_shards=2, n_records=6)
        spec = {"y": po.FixedLenFeature([1], stf.int64)}
        ds = (stf_data.TFRecordDataset(files, num_parallel_reads=2)
              .batch(3).parse_example(spec).prefetch(2))
        nxt = ds.make_one_shot_iterator().get_next()
        with stf.Session() as sess:
            a = sess.run(nxt)
            b = sess.run(nxt)
        np.testing.assert_array_equal(np.asarray(a["y"]).ravel(),
                                      [0, 1, 2])
        np.testing.assert_array_equal(np.asarray(b["y"]).ravel(),
                                      [3, 4, 5])


class TestAutotuneAndMetrics:
    def test_autotune_accepted_everywhere(self):
        ds = (stf_data.Dataset.from_tensor_slices(np.arange(30))
              .map(lambda x: x * 2, num_parallel_calls=AUTOTUNE)
              .prefetch(AUTOTUNE))
        assert [int(x) for x in ds] == [2 * i for i in range(30)]

    def test_autotune_thread_starts_and_widens_bottleneck(self):
        # Regression: knobs register lazily (inside stage generator
        # bodies, on the first element), so gating the autotuner spawn
        # on the knob list at pipeline-build time left AUTOTUNE
        # permanently pinned at initial parallelism.
        adj = monitoring.get_metric("/stf/data/autotune_adjustments")
        before = sum(adj.snapshot()["cells"].values())
        ds = (stf_data.Dataset.from_tensor_slices(np.arange(120))
              .map(lambda x: (time.sleep(0.005), x * 2)[1],
                   num_parallel_calls=AUTOTUNE)
              .prefetch(AUTOTUNE))
        it = iter(ds)
        got = [int(next(it)) for _ in range(60)]
        assert any(t.name == "stf_data_autotune"
                   for t in threading.enumerate())
        got += [int(x) for x in it]
        assert got == [2 * i for i in range(120)]
        after = sum(adj.snapshot()["cells"].values())
        assert after > before  # the slow map stage got widened

    def test_ring_occupancy_reported(self):
        # Regression: /stf/data/buffer_occupancy was only written by the
        # autotuner tick (AUTOTUNE prefetch rings), never by fixed-size
        # rings — the ring itself must report occupancy on put/get.
        ds = stf_data.Dataset.from_tensor_slices(np.arange(40)).prefetch(4)
        it = iter(ds)
        occ = 0
        deadline = time.time() + 5.0
        while occ < 1 and time.time() < deadline:
            next(it)
            cells = monitoring.get_metric(
                "/stf/data/buffer_occupancy").snapshot()["cells"]
            occ = max((v for k, v in cells.items()
                       if k.startswith("prefetch")), default=0)
            time.sleep(0.01)
        it.close()
        assert occ >= 1

    def test_stage_metrics_populated(self, tmp_path):
        files = _write_shards(tmp_path, n_shards=2, n_records=10)
        rec0 = monitoring.get_metric(
            "/stf/data/records_read").get_cell().value()
        list(stf_data.TFRecordDataset(files, num_parallel_reads=2)
             .map(lambda b: b, num_parallel_calls=2).prefetch(2))
        assert monitoring.get_metric(
            "/stf/data/records_read").get_cell().value() == rec0 + 20
        cells = monitoring.get_metric(
            "/stf/data/elements").snapshot()["cells"]
        assert any(k.startswith("tfrecord") for k in cells)
        assert any(k.startswith("pmap") for k in cells)
        assert any(k.startswith("prefetch") for k in cells)
        par = monitoring.get_metric(
            "/stf/data/parallelism").snapshot()["cells"]
        assert par  # gauges registered for parallel stages

    def test_worker_spans_land_in_parent_trace(self, tmp_path):
        files = _write_shards(tmp_path, n_shards=2, n_records=5)
        with monitoring.trace_collection() as buf:
            list(stf_data.TFRecordDataset(files, num_parallel_reads=2)
                 .batch(5).parse_example(
                     {"y": po.FixedLenFeature([1], stf.int64)}))
        names = {s["name"] for s in buf.spans}
        assert "data_read_shard" in names
        assert "parse_example_batch" in names

    def test_pipeline_iterator_close_idempotent(self):
        ds = stf_data.Dataset.from_tensor_slices(
            np.arange(100)).prefetch(2)
        it = iter(ds)
        assert int(next(it)) == 0
        it.close()
        it.close()
        with pytest.raises(StopIteration):
            next(it)


class TestSharedPoolNoDeadlock:
    def test_two_unordered_stages_saturating_pool(self):
        """Regression: unordered-map completion callbacks used to block
        in ring.put ON POOL WORKER THREADS; once the ring filled, up to
        pool_size callbacks parked and occupied every worker, so a
        second pool-using stage could never execute and the pipeline
        hung permanently. Callbacks must never block."""
        import threading
        import time

        from simple_tensorflow_tpu.data import pipeline as pl

        p = pl.pool_size()
        n = 6 * p + 40

        def slow_double(x):
            time.sleep(0.002)
            return x * 2

        ds = (stf_data.Dataset.from_tensor_slices(np.arange(n))
              .map(lambda x: x + 1, num_parallel_calls=p,
                   deterministic=False)
              .map(slow_double, num_parallel_calls=2, deterministic=False))
        got = []

        def consume():
            for x in ds:
                got.append(int(x))

        t = threading.Thread(target=consume, daemon=True)
        t.start()
        t.join(timeout=60)
        assert not t.is_alive(), "pipeline deadlocked (pool wedged)"
        assert sorted(got) == [(i + 1) * 2 for i in range(n)]


class TestArenaBatchAssembly:
    def test_batch_assembles_into_arena_slots(self):
        """The zero-copy handoff: a batch node with an alloc_pool stacks
        straight into C++ arena memory (pipeline.ArenaBatch carries the
        slot for post-transfer recycling)."""
        from simple_tensorflow_tpu.data import pipeline as pl
        from simple_tensorflow_tpu.runtime import native

        if not native.available():
            pytest.skip("native runtime not built")
        ds = stf_data.Dataset.from_tensor_slices(
            np.arange(24, dtype=np.float32)).batch(4)
        pool = native.ArenaPool(slots=8)
        node = pl.Node("batch", ds._node.parent, ds._node.args)
        node.alloc_pool = pool
        out = list(pl.build_iterator(node, sequential=True))
        assert all(isinstance(b, pl.ArenaBatch) for b in out)
        for i, b in enumerate(out):
            np.testing.assert_array_equal(
                b.value, np.arange(i * 4, i * 4 + 4, dtype=np.float32))
        pool.close()

    def test_padded_batch_not_arena_flagged(self):
        """Regression: prefetch_to_device keyed arena direct-assembly on
        node kind "batch"; padded_batch shares that kind but its stack
        fn ignores the allocator, so slots were acquired and transfer-
        gated while the batch was built in ordinary memory. Only
        alloc-capable stack fns may be cloned with an alloc_pool."""
        from simple_tensorflow_tpu.data.dataset import _stack_batch

        assert _stack_batch.supports_alloc is True
        b = stf_data.Dataset.from_tensor_slices(np.arange(8)).batch(4)
        sb = stf_data.Dataset.from_tensor_slices(np.arange(8)).superbatch(2)
        pb = stf_data.Dataset.from_tensor_slices(
            np.arange(8)).padded_batch(4)
        assert getattr(b._node.args[2], "supports_alloc", False)
        assert getattr(sb._node.args[2], "supports_alloc", False)
        assert not getattr(pb._node.args[2], "supports_alloc", False)


class TestCompileCacheWiring:
    def test_config_param_and_env(self, tmp_path, monkeypatch):
        import jax

        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        try:
            cache_dir = str(tmp_path / "cc")
            cfg = stf.ConfigProto(compile_cache_dir=cache_dir)
            with stf.Session(config=cfg):
                pass
            assert os.path.isdir(cache_dir)
            assert jax.config.jax_compilation_cache_dir == cache_dir
            env_dir = str(tmp_path / "env_cc")
            monkeypatch.setenv("STF_COMPILE_CACHE", env_dir)
            with stf.Session():
                pass
            assert jax.config.jax_compilation_cache_dir == env_dir
        finally:
            # tmp_path is deleted after the test — don't leave the
            # process-global cache pointing into it
            jax.config.update("jax_compilation_cache_dir", None)

    def test_jax_env_var_wins_and_is_never_overwritten(
            self, tmp_path, monkeypatch):
        """The one rule (compiler/aot.py): with
        JAX_COMPILATION_CACHE_DIR set, no stf path sets another
        directory — not ConfigProto, not STF_COMPILE_CACHE, not the
        checkout default."""
        import jax
        from simple_tensorflow_tpu.compiler import aot

        outside = str(tmp_path / "outside")
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", outside)
        monkeypatch.setenv("STF_COMPILE_CACHE", str(tmp_path / "env_cc"))
        monkeypatch.setattr(aot, "_persistent_cache_dir", None)
        before = jax.config.jax_compilation_cache_dir
        updates = []
        real_update = jax.config.update

        def spy(name, value):
            updates.append(name)
            return real_update(name, value)

        monkeypatch.setattr(jax.config, "update", spy)
        cfg = stf.ConfigProto(compile_cache_dir=str(tmp_path / "cc"))
        with stf.Session(config=cfg):
            pass
        assert aot.enable_persistent_cache() == outside
        assert not updates      # nor its thresholds: JAX's handling stands
        assert jax.config.jax_compilation_cache_dir == before
        assert aot.persistent_cache_dir() == outside

    def test_default_is_the_checkout_cache(self, monkeypatch):
        import jax
        from simple_tensorflow_tpu.compiler import aot

        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        monkeypatch.setattr(aot, "_persistent_cache_dir", None)
        repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        try:
            assert aot.enable_persistent_cache() == os.path.join(
                repo, ".jax_cache")
            assert jax.config.jax_compilation_cache_dir == os.path.join(
                repo, ".jax_cache")
        finally:
            jax.config.update("jax_compilation_cache_dir", None)
