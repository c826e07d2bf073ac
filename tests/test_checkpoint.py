"""stf.checkpoint: atomic commit protocol, async saves, crash
injection, CheckpointManager retention/verification, preemption
(ISSUE 10)."""

import json
import os
import signal
import subprocess
import sys
import textwrap

import numpy as np
import pytest

import simple_tensorflow_tpu as stf
from simple_tensorflow_tpu import checkpoint as ckpt
from simple_tensorflow_tpu.checkpoint import atomic
from simple_tensorflow_tpu.train.saver import (latest_checkpoint,
                                               load_checkpoint_values)


@pytest.fixture(autouse=True)
def fresh_state():
    stf.reset_default_graph()
    yield
    atomic.set_fault_hook(None)
    ckpt.reset_preemption_state()
    ckpt.uninstall_preemption_handler()
    ckpt.get_writer().wait_until_finished(timeout=10.0)


def _model(lr=0.25):
    """Tiny Adam model: variables + optimizer slots + global_step."""
    gs = stf.train.get_or_create_global_step()
    v = stf.Variable(stf.constant([1.0, 2.0]), name="cv")
    loss = stf.reduce_sum(stf.square(v._ref))
    train = stf.train.AdamOptimizer(lr).minimize(loss, global_step=gs)
    return gs, v, train


class TestAtomicCommit:
    def test_crash_at_every_point_leaves_old_or_new(self, tmp_path):
        path = str(tmp_path / "f.bin")
        atomic.atomic_write_bytes(path, b"v1")
        assert open(path, "rb").read() == b"v1"
        for point in atomic.COMMIT_POINTS:
            atomic.atomic_write_bytes(path, b"v1")

            def boom(p, _target=f"f.bin:{point}"):
                if p == _target:
                    raise RuntimeError(f"injected at {_target}")

            atomic.set_fault_hook(boom)
            with pytest.raises(RuntimeError):
                atomic.atomic_write_bytes(path, b"v2-longer-content")
            atomic.set_fault_hook(None)
            content = open(path, "rb").read()
            if point in ("replaced", "dir_synced"):
                assert content == b"v2-longer-content", point
            else:
                # never a partial write
                assert content == b"v1", point
        atomic.atomic_write_bytes(path, b"v3")
        assert open(path, "rb").read() == b"v3"

    def test_aborted_commit_cleans_tmp_file(self, tmp_path):
        path = str(tmp_path / "g.bin")

        def boom(p):
            if p.endswith(":wrote_tmp"):
                raise RuntimeError("injected")

        atomic.set_fault_hook(boom)
        with pytest.raises(RuntimeError):
            atomic.atomic_write_bytes(path, b"x")
        atomic.set_fault_hook(None)
        assert os.listdir(tmp_path) == []

    def test_two_threads_commit_one_path(self, tmp_path):
        """Two writers of one path in one process (the preemption
        handler's save and the saver hook's, each updating the
        ``checkpoint`` state file) never commit each other's temp file:
        every commit lands, the file is always one writer's whole."""
        import threading

        path = str(tmp_path / "state.json")
        errors = []

        def writer(tag):
            try:
                for i in range(300):
                    atomic.atomic_write_json(path, {"by": tag, "n": i},
                                             fsync=False)
            except BaseException as e:  # noqa: BLE001 — reported below
                errors.append(e)

        threads = [threading.Thread(target=writer, args=(t,),
                                    name=f"stf_test_commit_{t}")
                   for t in ("a", "b", "c")]
        for t in threads:
            t.start()
        for t in threads:
            t.join(60)
            assert not t.is_alive()
        assert errors == []
        assert json.load(open(path))["n"] == 299
        assert os.listdir(tmp_path) == ["state.json"]

    def test_checksum_detects_flip(self, tmp_path):
        data = os.urandom(4096)
        path = str(tmp_path / "c.bin")
        atomic.atomic_write_bytes(path, data)
        assert atomic.checksum_file(path) == atomic.checksum_bytes(data)
        flipped = bytearray(data)
        flipped[100] ^= 0xFF
        assert atomic.checksum_bytes(bytes(flipped)) != \
            atomic.checksum_bytes(data)


class TestAsyncSave:
    def test_async_matches_blocking_bit_for_bit(self, tmp_path):
        gs, v, train = _model()
        sess = stf.Session()
        sess.run(stf.global_variables_initializer())
        for _ in range(3):
            sess.run(train)
        blocking = stf.train.Saver()
        p_blk = blocking.save(sess, str(tmp_path / "blk" / "ckpt"),
                              global_step=gs, write_meta_graph=False)
        mgr = ckpt.CheckpointManager(str(tmp_path / "async"),
                                     async_save=True)
        p_async = mgr.save(sess, global_step=gs, blocking=True)
        a, b = load_checkpoint_values(p_blk), load_checkpoint_values(
            p_async)
        assert sorted(a) == sorted(b)
        assert any("Adam" in k or "beta" in k for k in a), \
            "optimizer slots must be part of the checkpoint"
        for k in a:
            np.testing.assert_array_equal(a[k], b[k])
        doc_a = json.load(open(p_blk + ".index.json"))
        doc_b = json.load(open(p_async + ".index.json"))
        assert doc_a["host_state"] == doc_b["host_state"]
        assert doc_b["checksum"].startswith("sha256:")
        assert doc_b["version"] >= 2

    def test_snapshot_is_barrier_consistent_under_donation(self, tmp_path):
        """The core async-correctness property: state mutated (and
        DONATED by fused windows) after save() returns must not leak
        into the checkpoint."""
        v = stf.Variable(stf.constant(np.zeros((64, 64), np.float32)),
                         name="dw")
        train = stf.assign_add(v._ref, stf.ones([64, 64]))
        sess = stf.Session()
        sess.run(stf.global_variables_initializer())
        sess.run_steps(train, n=4)  # warm fused path: donation active
        mgr = ckpt.CheckpointManager(str(tmp_path))
        prefix = mgr.save(sess)  # snapshot at value 4
        sess.run_steps(train, n=8)  # donates the pre-save arrays
        mgr.wait_until_finished()
        assert float(np.asarray(sess.run(v.value()))[0, 0]) == 12.0
        saved = load_checkpoint_values(prefix)["dw"]
        np.testing.assert_array_equal(saved,
                                      np.full((64, 64), 4.0, np.float32))
        assert mgr.verify(prefix) == []

    def test_write_error_surfaces_on_wait_and_next_save(self, tmp_path):
        gs, v, train = _model()
        sess = stf.Session()
        sess.run(stf.global_variables_initializer())
        mgr = ckpt.CheckpointManager(str(tmp_path))
        ok_prefix = mgr.save(sess, global_step=0, blocking=True)

        def boom(p):
            if p == "data:wrote_tmp":
                raise RuntimeError("disk on fire")

        atomic.set_fault_hook(boom)
        mgr.save(sess, global_step=1)
        with pytest.raises(RuntimeError, match="disk on fire"):
            mgr.wait_until_finished()
        atomic.set_fault_hook(None)
        # failed write never became latest
        assert latest_checkpoint(str(tmp_path)) == ok_prefix
        # the engine recovers: next save works
        p2 = mgr.save(sess, global_step=2, blocking=True)
        assert latest_checkpoint(str(tmp_path)) == p2
        snap = stf.monitoring.export()
        assert snap["/stf/checkpoint/write_errors"]["cells"][""] >= 1

    def test_saver_async_backend_shim(self, tmp_path):
        """Existing Saver call sites keep working with backend='async':
        same signature, same on-disk format, restore unchanged."""
        gs, v, train = _model()
        saver = stf.train.Saver(backend="async")
        sess = stf.Session()
        sess.run(stf.global_variables_initializer())
        sess.run(train)
        path = saver.save(sess, str(tmp_path / "m"), global_step=gs)
        saver.wait_until_finished()
        assert latest_checkpoint(str(tmp_path)) == path
        v_at_save = np.asarray(sess.run(v.value()))
        sess.run(train)
        saver.restore(sess, path)  # plain native restore reads it
        np.testing.assert_array_equal(np.asarray(sess.run(v.value())),
                                      v_at_save)

    def test_checkpoint_hook_async_by_default(self, tmp_path):
        gs, v, train = _model()
        events = []

        class Listener(stf.train.CheckpointSaverListener):
            def before_save(self, session, step):
                events.append(("before", step))

            def after_save(self, session, step):
                events.append(("after", step))

        hook = stf.train.CheckpointSaverHook(str(tmp_path), save_steps=2,
                                             listeners=[Listener()])
        with stf.train.MonitoredSession(
                session_creator=stf.train.ChiefSessionCreator(),
                hooks=[stf.train.StopAtStepHook(last_step=5), hook]) as ms:
            while not ms.should_stop():
                ms.run(train)
        # end() drains the writer: everything durable at context exit
        path = latest_checkpoint(str(tmp_path))
        assert path is not None and path.endswith("-5")
        assert ckpt.verify_checkpoint(path) == []
        assert ("before", 5) in events and ("after", 5) in events
        snap = stf.monitoring.export()
        assert snap["/stf/checkpoint/saves"]["cells"].get("async", 0) >= 1


_POINTS = [f"{label}:{point}"
           for label in ("data", "index", "state")
           for point in atomic.COMMIT_POINTS]


class TestCrashInjection:
    def test_randomized_writer_crashes_never_corrupt_latest(self, tmp_path):
        """ISSUE 10 satellite: kill the writer at randomized commit
        points mid-save; latest_checkpoint() must always restore a
        consistent, checksum-valid state matching a fully committed
        save."""
        rng = np.random.RandomState(
            int(os.environ.get("STF_CRASH_SEED", "20260804")))
        v = stf.Variable(stf.constant([0.0]), name="cw")
        bump = stf.assign_add(v._ref, stf.constant([1.0]))
        sess = stf.Session()
        sess.run(stf.global_variables_initializer())
        mgr = ckpt.CheckpointManager(str(tmp_path), max_to_keep=3)
        committed = {}  # prefix -> barrier value

        def attempt(step, fault_point):
            barrier_val = float(np.asarray(sess.run(v.value()))[0])
            if fault_point is not None:
                def boom(p, _t=fault_point):
                    if p == _t:
                        raise RuntimeError(f"injected at {_t}")

                atomic.set_fault_hook(boom)
            try:
                prefix = mgr.save(sess, global_step=step)
                mgr.wait_until_finished()
                committed[prefix] = barrier_val
            except RuntimeError:
                # a crash AFTER the state-file replace still produced a
                # complete checkpoint: record it as committed
                if fault_point and fault_point.startswith("state:") and \
                        fault_point.split(":")[1] in ("replaced",
                                                      "dir_synced"):
                    committed[f"{mgr.directory}/model.ckpt-{step}"] = \
                        barrier_val
            finally:
                atomic.set_fault_hook(None)

        attempt(0, None)  # one clean save so latest always exists
        for step in range(1, 13):
            sess.run(bump)
            point = _POINTS[rng.randint(len(_POINTS))] \
                if rng.rand() < 0.8 else None
            attempt(step, point)
            latest = latest_checkpoint(str(tmp_path))
            assert latest is not None
            assert ckpt.verify_checkpoint(latest) == [], latest
            assert latest in committed, \
                f"latest {latest} points at a save that never fully " \
                f"committed (committed: {sorted(committed)})"
            val = load_checkpoint_values(latest)["cw"][0]
            assert val == committed[latest], latest
        # after the dust settles, a clean save becomes latest again
        sess.run(bump)
        final = mgr.save(sess, global_step=99, blocking=True)
        assert latest_checkpoint(str(tmp_path)) == final

    @pytest.mark.skipif(os.name != "posix",
                        reason="needs POSIX process semantics")
    def test_subprocess_kill_mid_commit(self, tmp_path):
        """os._exit in the middle of a commit (the real preemption-kill
        shape): the directory must stay consistent."""
        script = tmp_path / "killer.py"
        script.write_text(textwrap.dedent("""
            import os, sys
            os.environ.setdefault("JAX_PLATFORMS", "cpu")
            import simple_tensorflow_tpu as stf
            from simple_tensorflow_tpu import checkpoint as ckpt

            target, d = sys.argv[1], sys.argv[2]
            v = stf.Variable(stf.constant([0.0]), name="kw")
            bump = stf.assign_add(v._ref, stf.constant([1.0]))
            sess = stf.Session()
            sess.run(stf.global_variables_initializer())
            mgr = ckpt.CheckpointManager(d, async_save=False)
            mgr.save(sess, global_step=1)  # clean baseline
            sess.run(bump)
            if target != "none":
                ckpt.set_fault_hook(
                    lambda p: os._exit(137) if p == target else None)
            mgr.save(sess, global_step=2)
            print("COMPLETED", flush=True)
        """))
        env = {**os.environ, "JAX_PLATFORMS": "cpu",
               "PYTHONPATH": os.path.dirname(os.path.dirname(
                   os.path.abspath(__file__)))}
        for i, target in enumerate(["data:wrote_tmp", "index:synced_tmp",
                                    "state:open_tmp", "none"]):
            d = str(tmp_path / f"run{i}")
            r = subprocess.run(
                [sys.executable, str(script), target, d], env=env,
                capture_output=True, text=True, timeout=180)
            if target == "none":
                assert r.returncode == 0 and "COMPLETED" in r.stdout, \
                    r.stderr[-2000:]
            else:
                assert r.returncode == 137, (target, r.returncode,
                                             r.stderr[-2000:])
            latest = latest_checkpoint(d)
            assert latest is not None, (target, os.listdir(d))
            assert ckpt.verify_checkpoint(latest) == [], target
            # a kill mid-commit leaves the step-1 baseline latest; a
            # clean run advances to step 2 — either way the pointed-at
            # state is one that fully committed
            vals = load_checkpoint_values(latest)
            if target == "none":
                assert latest.endswith("-2") and vals["kw"][0] == 1.0
            else:
                assert latest.endswith("-1") and vals["kw"][0] == 0.0


class TestManager:
    def test_retention_across_async_saves(self, tmp_path):
        gs, v, train = _model()
        sess = stf.Session()
        sess.run(stf.global_variables_initializer())
        mgr = ckpt.CheckpointManager(str(tmp_path), max_to_keep=2)
        prefixes = []
        for _ in range(4):
            sess.run(train)
            prefixes.append(mgr.save(sess, global_step=gs))
        mgr.wait_until_finished()
        assert mgr.checkpoints == prefixes[-2:]
        for old in prefixes[:2]:
            assert not os.path.exists(old + ".stfz")
            assert not os.path.exists(old + ".index.json")
        for kept in prefixes[-2:]:
            assert ckpt.verify_checkpoint(kept) == []

    def test_restore_or_initialize_fresh_then_resume(self, tmp_path):
        gs, v, train = _model()
        mgr = ckpt.CheckpointManager(str(tmp_path))
        sess = stf.Session()
        out = mgr.restore_or_initialize(
            sess, init_op=stf.global_variables_initializer())
        assert out is None  # initialized fresh
        for _ in range(3):
            sess.run(train)
        v_save = np.asarray(sess.run(v.value()))
        mgr.save(sess, global_step=gs, blocking=True)

        sess2 = stf.Session()
        mgr2 = ckpt.CheckpointManager(str(tmp_path))
        path = mgr2.restore_or_initialize(
            sess2, init_op=stf.global_variables_initializer())
        assert path is not None and path.endswith("-3")
        np.testing.assert_array_equal(np.asarray(sess2.run(v.value())),
                                      v_save)
        assert int(np.asarray(sess2.run(gs.value()))) == 3

    def test_restore_or_initialize_falls_back_past_corruption(
            self, tmp_path):
        gs, v, train = _model()
        sess = stf.Session()
        sess.run(stf.global_variables_initializer())
        mgr = ckpt.CheckpointManager(str(tmp_path), max_to_keep=3)
        sess.run(train)
        good = mgr.save(sess, global_step=1, blocking=True)
        sess.run(train)
        bad = mgr.save(sess, global_step=2, blocking=True)
        with open(bad + ".stfz", "r+b") as f:
            f.seek(40)
            byte = f.read(1)
            f.seek(40)
            f.write(bytes([byte[0] ^ 0xFF]))
        assert mgr.verify(bad) != []
        sess2 = stf.Session()
        path = mgr.restore_or_initialize(
            sess2, init_op=stf.global_variables_initializer())
        assert path == good  # corrupt latest skipped, older restored
        snap = stf.monitoring.export()
        assert snap["/stf/checkpoint/integrity_failures"]["cells"].get(
            "checksum_mismatch", 0) >= 1

    def test_restore_verify_raises_dataloss(self, tmp_path):
        gs, v, train = _model()
        sess = stf.Session()
        sess.run(stf.global_variables_initializer())
        mgr = ckpt.CheckpointManager(str(tmp_path))
        p = mgr.save(sess, global_step=1, blocking=True)
        with open(p + ".stfz", "r+b") as f:
            f.seek(10)
            f.write(b"\xde\xad")
        with pytest.raises(stf.errors.DataLossError):
            mgr.restore(stf.Session())
        # plain Saver.restore checks the checksum too
        with pytest.raises(stf.errors.DataLossError):
            stf.train.Saver().restore(stf.Session(), p)

    def test_manager_interops_with_train_saver(self, tmp_path):
        gs, v, train = _model()
        sess = stf.Session()
        sess.run(stf.global_variables_initializer())
        sess.run(train)
        mgr = ckpt.CheckpointManager(str(tmp_path))
        p = mgr.save(sess, global_step=gs, blocking=True)
        assert stf.train.latest_checkpoint(str(tmp_path)) == p
        v_save = np.asarray(sess.run(v.value()))
        sess.run(train)
        stf.train.Saver().restore(sess, p)
        np.testing.assert_array_equal(np.asarray(sess.run(v.value())),
                                      v_save)

    def test_manager_adopts_existing_directory(self, tmp_path):
        gs, v, train = _model()
        sess = stf.Session()
        sess.run(stf.global_variables_initializer())
        m1 = ckpt.CheckpointManager(str(tmp_path), max_to_keep=2)
        for step in range(2):
            m1.save(sess, global_step=step, blocking=True)
        # a new manager (fresh process in real life) adopts them, and
        # retention keeps counting from there
        m2 = ckpt.CheckpointManager(str(tmp_path), max_to_keep=2)
        assert len(m2.checkpoints) == 2
        m2.save(sess, global_step=2, blocking=True)
        assert len(m2.checkpoints) == 2
        assert not os.path.exists(str(tmp_path / "model.ckpt-0.stfz"))


class TestPreemption:
    def test_request_preemption_drains_saves_stops(self, tmp_path):
        gs, v, train = _model()
        handler = ckpt.PreemptionHandler(checkpoint_dir=str(tmp_path),
                                         install=False)
        cfg = stf.ConfigProto(loop_fusion_steps=8)
        n_calls = 0
        with stf.train.MonitoredSession(
                session_creator=stf.train.ChiefSessionCreator(config=cfg),
                hooks=[stf.train.StopAtStepHook(last_step=100),
                       handler]) as ms:
            while not ms.should_stop():
                ms.run(train)
                n_calls += 1
                if n_calls == 3:
                    ckpt.request_preemption()
            stopped_gs = int(np.asarray(
                ms.raw_session.variable_value("global_step")))
        assert stopped_gs < 100  # preemption, not StopAtStep
        assert handler.last_saved_prefix is not None
        assert handler.last_saved_prefix.endswith(f"-{stopped_gs}")
        assert ckpt.verify_checkpoint(handler.last_saved_prefix) == []
        doc = json.load(open(handler.last_saved_prefix + ".index.json"))
        assert "rng_run_counter" in doc["host_state"]
        snap = stf.monitoring.export()
        assert snap["/stf/checkpoint/preemptions"]["cells"][""] >= 1

    def test_preemption_vote_drops_window_to_one(self):
        handler = ckpt.PreemptionHandler(checkpoint_dir="/tmp/x",
                                         install=False)
        assert handler.until_next_trigger(10) == 1 << 30
        ckpt.request_preemption()
        assert handler.until_next_trigger(10) == 1

    @pytest.mark.skipif(os.name != "posix",
                        reason="needs POSIX signals")
    def test_sigterm_chains_user_handler_and_survives(self):
        called = []
        prev = signal.signal(signal.SIGTERM,
                             lambda s, f: called.append(s))
        try:
            assert ckpt.install_preemption_handler()
            signal.raise_signal(signal.SIGTERM)
            assert ckpt.preemption_requested()
            assert called == [signal.SIGTERM]  # user handler chained
        finally:
            ckpt.uninstall_preemption_handler()
            signal.signal(signal.SIGTERM, prev)

    @pytest.mark.skipif(os.name != "posix",
                        reason="needs POSIX signals")
    def test_sigterm_absorbs_telemetry_terminate_tail(self, tmp_path,
                                                      monkeypatch):
        """With telemetry's dump-then-terminate handler installed first,
        the preemption handler must dump WITHOUT letting the process
        die — the whole point is the graceful drain."""
        from simple_tensorflow_tpu.telemetry import recorder as rec_mod

        monkeypatch.setenv("STF_FLIGHT_RECORDER_DIR", str(tmp_path))
        prev = signal.getsignal(signal.SIGTERM)
        installed = rec_mod.install_signal_handlers()
        try:
            assert installed
            assert ckpt.install_preemption_handler()
            signal.raise_signal(signal.SIGTERM)
            # still alive, preemption requested, forensics dumped
            assert ckpt.preemption_requested()
            dump = rec_mod.get_recorder().last_dump_path
            assert dump and os.path.dirname(dump) == str(tmp_path)
        finally:
            ckpt.uninstall_preemption_handler()
            signal.signal(signal.SIGTERM, prev)
            rec_mod._signals_installed = False
            rec_mod._installed_handler = None


if __name__ == "__main__":
    pytest.main([__file__, "-v"])
