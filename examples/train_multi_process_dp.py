#!/usr/bin/env python
"""Multi-process data-parallel training through stf.train.Server +
Session(target) — the TF-1 cluster workflow, TPU-native.

The reference attaches `tf.Session("grpc://host:2222")` to a grpc
master that partitions the graph across workers. stf maps the same
surface to SPMD: every process runs the SAME script, `stf.train.Server`
performs the jax.distributed bootstrap (coordinator = worker 0), and a
`stf.Session(server.target)` then sees the GLOBAL device mesh — one
program, all hosts' devices, XLA collectives over ICI/DCN.

Run (single machine, 2 processes, 1 CPU device each):

    python examples/train_multi_process_dp.py

The parent spawns both workers and checks they converge to the same
loss on a variable sharded across BOTH processes' devices.
"""

import json
import os
import socket
import subprocess
import sys
import threading
import time

_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, _REPO_ROOT)


def worker(task_index: int, cluster: str) -> None:
    os.environ["JAX_PLATFORMS"] = "cpu"
    import jax
    import numpy as np

    import simple_tensorflow_tpu as stf
    from simple_tensorflow_tpu import parallel
    from simple_tensorflow_tpu.train import server_lib

    workers = cluster.split(",")
    server = server_lib.Server({"worker": workers}, job_name="worker",
                               task_index=task_index, start=True)

    # the bootstrap gives every process the global device view
    devices = jax.devices()
    n = len(devices)
    assert n == len(workers), (n, workers)

    mesh = parallel.Mesh({"dp": n}, devices=devices)
    rng = np.random.RandomState(0)  # identical on every process (SPMD)
    with mesh:
        x = stf.constant(rng.randn(8 * n, 16).astype(np.float32))
        t = stf.constant(rng.randn(8 * n, 1).astype(np.float32))
        w = stf.Variable(np.zeros((16, 1), np.float32), name="w")
        # batch rows sharded over dp; w replicated; psum'd grads via
        # GSPMD — the sync_replicas recipe without a parameter server
        x = parallel.with_sharding_constraint(x, "dp", None)
        loss = stf.reduce_mean(stf.square(stf.matmul(x, w) - t))
        train = stf.train.GradientDescentOptimizer(0.05).minimize(loss)

        sess = stf.Session(server.target)  # routes/validates the target
        sess.run(stf.global_variables_initializer())
        l0 = float(np.asarray(sess.run(loss)))
        for _ in range(30):
            sess.run(train)
        l1 = float(np.asarray(sess.run(loss)))
    print(json.dumps({"task": task_index, "n_devices": n,
                      "loss0": round(l0, 5), "loss1": round(l1, 5),
                      "target": server.target}), flush=True)


def main() -> int:
    # only worker 0's address is ever bound (the coordinator); hold the
    # probe socket until just before spawning to narrow the reuse race
    probe = socket.socket()
    probe.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEADDR, 1)
    probe.bind(("127.0.0.1", 0))
    port = probe.getsockname()[1]
    cluster = f"127.0.0.1:{port},127.0.0.1:{port}"
    env = {k: v for k, v in os.environ.items()
           if k != "XLA_FLAGS"}
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = _REPO_ROOT + os.pathsep + env.get("PYTHONPATH",
                                                          "")
    probe.close()
    procs = [subprocess.Popen(
        [sys.executable, os.path.abspath(__file__), "--worker", str(i),
         cluster], stdout=subprocess.PIPE, stderr=subprocess.PIPE,
        text=True, env=env) for i in range(2)]
    # drain every worker's pipes CONCURRENTLY: waiting on worker 0 while
    # worker 1's stderr fills its pipe buffer would block worker 1 inside
    # write() mid-collective and deadlock the SPMD step until the timeout
    outs = [None] * len(procs)

    def _drain(i):
        outs[i] = procs[i].communicate()

    drains = [threading.Thread(target=_drain, args=(i,), daemon=True)
              for i in range(len(procs))]
    results = []
    try:
        for d in drains:
            d.start()
        deadline = time.perf_counter() + 300.0
        for d in drains:
            d.join(timeout=max(1.0, deadline - time.perf_counter()))
        for i, p in enumerate(procs):
            if outs[i] is None:  # still running at the deadline
                print(f"worker {i} timed out", file=sys.stderr)
                return 1
            out, err = outs[i]
            if p.returncode != 0:
                print(err[-2000:], file=sys.stderr)
                return 1
            results.append(json.loads(
                [line for line in out.splitlines()
                 if line.startswith("{")][-1]))
    finally:
        for p in procs:  # a dead/late/hung sibling must not linger
            if p.poll() is None:
                p.kill()
    assert all(r["n_devices"] == 2 for r in results), results
    assert all(r["loss1"] < r["loss0"] for r in results), results
    # SPMD: both processes computed the identical global step
    assert results[0]["loss1"] == results[1]["loss1"], results
    print("multi-process dp OK:", json.dumps(results))
    return 0


if __name__ == "__main__":
    if len(sys.argv) > 1 and sys.argv[1] == "--worker":
        worker(int(sys.argv[2]), sys.argv[3])
        sys.exit(0)
    sys.exit(main())
