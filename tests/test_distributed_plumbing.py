"""ClusterSpec/Server mapping and failure-detection behavior
(ref: python/training/server_lib.py:189 ClusterSpec,
core/distributed_runtime session-management failure semantics)."""

import os
import time

import numpy as np
import pytest

from simple_tensorflow_tpu.framework.errors import (DeadlineExceededError,
                                                    UnavailableError)
from simple_tensorflow_tpu.parallel.failure_detection import (Heartbeat,
                                                              StepWatchdog)
from simple_tensorflow_tpu.train import server_lib

# jax's CPU backend cannot run computations that span processes — the
# two-process smoke tests bootstrap fine but any cross-process program
# fails with this runtime error. Skip (with the reason) instead of
# failing: the code path under test is exercised for real on TPU pods.
_NO_MULTIPROCESS_MARKER = "computations aren't implemented"


def _skip_if_backend_lacks_multiprocess(err: str):
    if _NO_MULTIPROCESS_MARKER in err:
        pytest.skip("backend does not support multiprocess computations "
                    "(jax CPU backend: \"Multiprocess computations aren't "
                    "implemented\")")


class TestClusterSpec:
    def test_from_dict_lists(self):
        cs = server_lib.ClusterSpec(
            {"worker": ["w0:2222", "w1:2222"], "eval": ["e0:2222"]})
        assert sorted(cs.jobs) == ["eval", "worker"]
        assert cs.num_tasks("worker") == 2
        assert cs.task_indices("worker") == [0, 1]
        assert cs.task_address("worker", 1) == "w1:2222"
        assert cs.job_tasks("worker") == ["w0:2222", "w1:2222"]
        assert cs.as_dict() == {"worker": ["w0:2222", "w1:2222"],
                                "eval": ["e0:2222"]}

    def test_from_sparse_task_dict(self):
        # TF allows sparse task indices: {"worker": {1: "w1", 3: "w3"}}
        cs = server_lib.ClusterSpec({"worker": {3: "w3:2222", 1: "w1:2222"}})
        assert cs.task_indices("worker") == [1, 3]
        assert cs.job_tasks("worker") == ["w1:2222", "w3:2222"]
        assert cs.task_address("worker", 3) == "w3:2222"

    def test_copy_and_equality(self):
        a = server_lib.ClusterSpec({"worker": ["w0"]})
        b = server_lib.ClusterSpec(a)
        assert a == b and a is not b
        assert bool(a)
        assert not bool(server_lib.ClusterSpec({}))

    def test_rejects_non_dict(self):
        with pytest.raises(TypeError):
            server_lib.ClusterSpec(["w0:2222"])


class TestServer:
    def test_ps_job_rejected_with_guidance(self):
        with pytest.raises(ValueError, match="fsdp"):
            server_lib.Server({"worker": ["w0:1"], "ps": ["p0:1"]},
                              start=False)

    def test_single_worker_start_is_local_noop(self):
        # one worker: no jax.distributed.initialize, start() succeeds
        old = server_lib.Server._started
        server_lib.Server._started = False
        try:
            s = server_lib.Server({"worker": ["localhost:0"]}, start=True)
            assert server_lib.Server._started
            assert s.target == "stf://worker:0"
            sd = s.server_def
            assert sd.job_name == "worker" and sd.task_index == 0
            assert sd.cluster.as_dict() == {"worker": ["localhost:0"]}
        finally:
            server_lib.Server._started = old

    def test_create_local_server(self):
        old = server_lib.Server._started
        server_lib.Server._started = False
        try:
            s = server_lib.Server.create_local_server()
            assert s.target.startswith("stf://worker")
        finally:
            server_lib.Server._started = old


class TestHeartbeat:
    def test_beat_and_check(self):
        hb = Heartbeat(interval_secs=0.01)
        hb.beat()
        hb.check(hb.last_beat, max_age_secs=5.0)  # fresh: no raise
        stale = time.time() - 60.0
        with pytest.raises(UnavailableError, match="presumed dead"):
            hb.check(stale, max_age_secs=10.0)

    def test_background_thread_stamps(self):
        hb = Heartbeat(interval_secs=0.01).start()
        try:
            before = hb.last_beat
            time.sleep(0.1)
            assert hb.last_beat > before
        finally:
            hb.stop()


class TestStepWatchdog:
    def test_fires_on_stall_and_raises_at_step_done(self):
        fired = []
        wd = StepWatchdog(deadline_secs=0.05, poll_secs=0.01,
                          on_timeout=lambda stalled: fired.append(stalled))
        wd.start()
        try:
            time.sleep(0.2)  # stall past the deadline
            assert wd.timed_out
            assert fired and fired[0] > 0.05
            with pytest.raises(DeadlineExceededError, match="deadline"):
                wd.step_done()
        finally:
            wd.stop()

    def test_regular_steps_keep_it_quiet(self):
        wd = StepWatchdog(deadline_secs=0.2, poll_secs=0.01).start()
        try:
            for _ in range(5):
                time.sleep(0.02)
                wd.step_done()
            assert not wd.timed_out
        finally:
            wd.stop()


class TestTwoProcessDistributed:
    """2-process jax.distributed CPU smoke: Server ->
    jax.distributed.initialize across REAL processes, coordinator on
    worker:0; each process must see the global device view."""

    def test_two_process_server_init(self, tmp_path):
        import socket
        import subprocess
        import sys

        with socket.socket() as s:
            s.bind(("127.0.0.1", 0))
            port = s.getsockname()[1]
        cluster = f"127.0.0.1:{port}"
        script = (
            "import os, sys, json\n"
            "os.environ['JAX_PLATFORMS'] = 'cpu'\n"
            "import jax\n"
            "from simple_tensorflow_tpu.train import server_lib\n"
            "server_lib.Server._started = False\n"
            "idx = int(sys.argv[1])\n"
            "s = server_lib.Server(\n"
            "    {'worker': ['%s', '%s']},\n"
            "    job_name='worker', task_index=idx, start=True)\n"
            "print(json.dumps({'pid': idx,\n"
            "                  'n_proc': jax.process_count(),\n"
            "                  'n_dev': len(jax.devices()),\n"
            "                  'target': s.target}))\n" % (cluster, cluster))
        env = dict(os.environ)
        env["JAX_PLATFORMS"] = "cpu"
        env.pop("XLA_FLAGS", None)  # one device per process
        repo_root = os.path.dirname(os.path.dirname(
            os.path.abspath(__file__)))
        env["PYTHONPATH"] = repo_root + os.pathsep + env.get("PYTHONPATH",
                                                             "")
        procs = [subprocess.Popen(
            [sys.executable, "-c", script, str(i)],
            stdout=subprocess.PIPE, stderr=subprocess.PIPE,
            text=True, env=env, cwd=str(tmp_path))
            for i in range(2)]
        outs = []
        try:
            for p in procs:
                out, err = p.communicate(timeout=120)
                if p.returncode != 0:
                    _skip_if_backend_lacks_multiprocess(err)
                assert p.returncode == 0, f"rc={p.returncode}: {err[-1500:]}"
                outs.append(out)
        finally:
            for p in procs:
                if p.poll() is None:
                    p.kill()
        import json as _json

        for out in outs:
            line = [l for l in out.splitlines() if l.startswith("{")][-1]
            d = _json.loads(line)
            assert d["n_proc"] == 2, d
            assert d["n_dev"] == 2, d  # global view: both processes' devices
            assert d["target"].startswith("stf://worker:")


class TestSessionTargetRouting:
    """Session(target) must route or raise — silently
    running local on a non-empty target is the one forbidden outcome
    (ref: core/distributed_runtime/rpc/grpc_session.cc)."""

    def _fresh(self):
        old = (server_lib.Server._started, server_lib.Server._coordinator)
        server_lib.Server._started = False
        server_lib.Server._coordinator = None
        return old

    def _restore(self, old):
        server_lib.Server._started, server_lib.Server._coordinator = old

    def test_unknown_scheme_raises_unimplemented(self):
        import simple_tensorflow_tpu as stf
        from simple_tensorflow_tpu.framework import errors

        with pytest.raises(errors.UnimplementedError, match="not supported"):
            stf.Session("ipc:///tmp/sock")

    def test_stf_target_requires_server(self):
        import simple_tensorflow_tpu as stf
        from simple_tensorflow_tpu.framework import errors

        old = self._fresh()
        try:
            with pytest.raises(errors.FailedPreconditionError,
                               match="no Server has started"):
                stf.Session("stf://worker:0")
        finally:
            self._restore(old)

    def test_grpc_target_without_bootstrap_raises(self):
        import simple_tensorflow_tpu as stf
        from simple_tensorflow_tpu.framework import errors

        old = self._fresh()
        try:
            with pytest.raises(errors.FailedPreconditionError,
                               match="bootstrap"):
                stf.Session("grpc://10.0.0.1:2222")
        finally:
            self._restore(old)

    def test_grpc_target_mismatched_coordinator_raises(self):
        import simple_tensorflow_tpu as stf
        from simple_tensorflow_tpu.framework import errors

        old = self._fresh()
        try:
            server_lib.Server._started = True
            server_lib.Server._coordinator = "127.0.0.1:1111"
            with pytest.raises(errors.InvalidArgumentError,
                               match="does not match"):
                stf.Session("grpc://127.0.0.1:2222")
            stf.Session("grpc://127.0.0.1:1111").close()  # match: accepted
        finally:
            self._restore(old)

    def test_server_target_accepted_after_local_server(self):
        import simple_tensorflow_tpu as stf

        old = self._fresh()
        try:
            s = server_lib.Server.create_local_server()
            sess = stf.Session(s.target)
            stf.reset_default_graph()
            sess.close()
        finally:
            self._restore(old)
    def test_two_process_session_step_on_global_mesh(self, tmp_path):
        """Process B (and A — SPMD) runs stf.Session(server.target) and
        executes a training step on the GLOBAL 2-device mesh: a variable
        sharded across both processes' devices updates, loss decreases."""
        import socket
        import subprocess
        import sys

        with socket.socket() as s:
            s.bind(("127.0.0.1", 0))
            port = s.getsockname()[1]
        cluster = f"127.0.0.1:{port}"
        script = (
            "import os, sys, json\n"
            "os.environ['JAX_PLATFORMS'] = 'cpu'\n"
            "import numpy as np\n"
            "import jax\n"
            "import simple_tensorflow_tpu as stf\n"
            "from simple_tensorflow_tpu import parallel\n"
            "from simple_tensorflow_tpu.train import server_lib\n"
            "server_lib.Server._started = False\n"
            "idx = int(sys.argv[1])\n"
            "srv = server_lib.Server(\n"
            "    {'worker': ['%s', '%s']},\n"
            "    job_name='worker', task_index=idx, start=True)\n"
            "devices = jax.devices()\n"
            "assert len(devices) == 2, devices\n"
            "mesh = parallel.Mesh({'dp': 2}, devices=devices)\n"
            "with mesh:\n"
            "    w0 = np.arange(8, dtype=np.float32).reshape(4, 2) * 0.3\n"
            "    W = stf.Variable(w0, name='W')\n"
            "    parallel.shard_variable(W, 'dp', None)\n"
            "    loss = stf.reduce_mean(stf.square(W._ref))\n"
            "    train = stf.train.GradientDescentOptimizer(0.5)"
            ".minimize(loss)\n"
            "    sess = stf.Session(srv.target)\n"
            "    sess.run(stf.global_variables_initializer())\n"
            "    l0 = float(np.asarray(sess.run(loss)))\n"
            "    sess.run(train)\n"
            "    l1 = float(np.asarray(sess.run(loss)))\n"
            "    arr = sess._variable_store.values['W']\n"
            "    n_dev = len(arr.sharding.device_set)\n"
            "print(json.dumps({'pid': idx, 'l0': l0, 'l1': l1,\n"
            "                  'w_devices': n_dev,\n"
            "                  'n_proc': jax.process_count()}))\n"
            % (cluster, cluster))
        env = dict(os.environ)
        env["JAX_PLATFORMS"] = "cpu"
        env.pop("XLA_FLAGS", None)  # one device per process
        repo_root = os.path.dirname(os.path.dirname(
            os.path.abspath(__file__)))
        env["PYTHONPATH"] = repo_root + os.pathsep + env.get("PYTHONPATH",
                                                             "")
        procs = [subprocess.Popen(
            [sys.executable, "-c", script, str(i)],
            stdout=subprocess.PIPE, stderr=subprocess.PIPE,
            text=True, env=env, cwd=str(tmp_path))
            for i in range(2)]
        outs = []
        try:
            for p in procs:
                out, err = p.communicate(timeout=180)
                if p.returncode != 0:
                    _skip_if_backend_lacks_multiprocess(err)
                assert p.returncode == 0, f"rc={p.returncode}: {err[-2000:]}"
                outs.append(out)
        finally:
            for p in procs:
                if p.poll() is None:
                    p.kill()
        import json as _json

        for out in outs:
            line = [l for l in out.splitlines() if l.startswith("{")][-1]
            d = _json.loads(line)
            assert d["n_proc"] == 2, d
            assert d["w_devices"] == 2, d  # W really spans both processes
            assert d["l1"] < d["l0"], d   # the global-mesh step trained
