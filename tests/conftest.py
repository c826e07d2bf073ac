"""Test config: force an 8-device virtual CPU mesh so multi-chip sharding
tests run without TPU hardware (SURVEY.md §4), and keep tests off the real
chip."""

import os

flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8").strip()
os.environ["JAX_PLATFORMS"] = "cpu"

import jax  # noqa: E402

assert len(jax.devices()) == 8, jax.devices()

import gc  # noqa: E402
import re  # noqa: E402
import threading  # noqa: E402
import time  # noqa: E402

import pytest  # noqa: E402


@pytest.fixture(autouse=True, scope="module")
def _no_pipeline_leaks():
    """Leak hygiene (ISSUE 6 satellite; serving added in ISSUE 7,
    telemetry in ISSUE 8, sync/thread-naming in ISSUE 18): after each
    test module, no pipeline stage / serving batcher / telemetry
    threads may still be running, every PipelineIterator must be
    closed, every ModelServer shut down, and the telemetry HTTP server
    stopped (an open server pins its listener + connection threads).
    The watchdog monitor thread is lazy process-global infrastructure:
    the fixture STOPS it after each module (re-arming restarts it) and
    asserts the stop works — clean shutdown is part of its contract.

    ISSUE 18 adds two global invariants: no NEW default-named
    (``Thread-N``) threads may survive the module — every runtime
    thread must carry an ``stf_``-prefixed name so wedge dumps and the
    leak scan can attribute it — and no sync.Lock may still be held at
    teardown (a held lock here means a thread died holding it or a
    context manager leaked)."""
    baseline_threads = {t.ident for t in threading.enumerate()}
    yield
    from simple_tensorflow_tpu import checkpoint as ckpt_mod
    from simple_tensorflow_tpu import telemetry
    from simple_tensorflow_tpu.data import pipeline
    from simple_tensorflow_tpu.serving import server as serving_server

    # dropped-but-uncollected iterators/servers are not leaks: GC close
    # is part of the contract, so drive it before judging
    gc.collect()
    open_iters = [it for it in list(pipeline.live_iterators)
                  if not it.closed]
    for it in open_iters:  # don't poison subsequent modules
        it.close()
    open_servers = [s for s in list(serving_server.live_servers)
                    if not s.closed]
    for s in open_servers:
        s.close()
    from simple_tensorflow_tpu.serving import generative as serving_gen

    open_engines = [e for e in list(serving_gen.live_engines)
                    if not e.closed]
    for e in open_engines:
        e.close()
    # RecordInput readers are graph-scoped with no user-facing close in
    # the reference contract, so stragglers are reaped (not asserted):
    # close() stops the poll loop, the thread exits within one tick
    from simple_tensorflow_tpu.ops import data_flow_ops as _dfo

    for r in list(_dfo._live_record_inputs):
        if not r._closed:
            r.close()
    open_telemetry = telemetry.get_server() is not None
    telemetry.shutdown()  # stops the HTTP server AND the watchdog
    # checkpoint writer (ISSUE 10): drain + stop the stf_ckpt_writer
    # thread — clean shutdown is part of its contract; the next async
    # save lazily restarts it. Also clear any preemption flag / fault
    # hook a test left armed.
    ckpt_mod.get_writer().wait_until_finished(timeout=10.0)
    writer_stopped = ckpt_mod.shutdown_writer(timeout=5.0)
    ckpt_mod.reset_preemption_state()
    ckpt_mod.uninstall_preemption_handler()
    ckpt_mod.set_fault_hook(None)

    # stage threads are named stf_data_<stage>, batcher threads
    # stf_serving_batcher_<model>, telemetry threads stf_telemetry_*
    # (http listener, per-connection, watchdog); the shared worker pool
    # (thread_name_prefix stf_data_worker) is process-global by design
    # and exempt. Closed stages may need a moment to observe cancel.
    def stray():
        return [t for t in threading.enumerate()
                if ((t.name.startswith("stf_data_")
                     and not t.name.startswith("stf_data_worker"))
                    or t.name.startswith("stf_serving_")
                    or t.name.startswith("stf_telemetry_")
                    or t.name.startswith("stf_ckpt_"))
                and t.is_alive()]

    # NEW default-named threads (vs the module-entry baseline): jax /
    # pytest internals predate the module and are exempt; anything the
    # module spawned must be stf_-named (sync plane, ISSUE 18)
    _unnamed_re = re.compile(r"^Thread-\d+")

    def unnamed():
        return [t for t in threading.enumerate()
                if t.ident not in baseline_threads and t.is_alive()
                and not t.daemon and _unnamed_re.match(t.name)]

    deadline = time.monotonic() + 5.0
    while (stray() or unnamed()) and time.monotonic() < deadline:
        time.sleep(0.05)
    leaked = stray()
    leaked_unnamed = unnamed()
    # held-lock invariant: transient holds (a scraper mid-snapshot) get
    # a short grace window, then any survivor is a real leak
    from simple_tensorflow_tpu.platform import sync as _sync_mod

    held = _sync_mod.all_held_locks()
    held_deadline = time.monotonic() + 2.0
    while held and time.monotonic() < held_deadline:
        time.sleep(0.05)
        held = _sync_mod.all_held_locks()
    assert not open_iters, (
        "unclosed PipelineIterator(s) leaked by this test module "
        f"(close() them or drop all references): {open_iters!r}")
    assert not open_servers, (
        "open ModelServer(s) leaked by this test module (close() them "
        f"or use a context manager): {open_servers!r}")
    assert not open_engines, (
        "open GenerativeEngine(s) leaked by this test module (close() "
        f"them or use a context manager): {open_engines!r}")
    assert not open_telemetry, (
        "telemetry server left running by this test module — call "
        "stf.telemetry.stop() (or telemetry.shutdown()) in teardown")
    assert writer_stopped, (
        "stf_ckpt_writer did not stop within its deadline — a "
        "checkpoint write job is wedged")
    assert not leaked, (
        "leaked pipeline/serving/telemetry/checkpoint thread(s): "
        + ", ".join(t.name for t in leaked))
    assert not leaked_unnamed, (
        "surviving non-stf_-named thread(s) spawned by this test "
        "module (name them stf_<subsystem>_... so wedge dumps can "
        "attribute them): "
        + ", ".join(t.name for t in leaked_unnamed))
    assert not held, (
        "sync.Lock(s) still held at module teardown (a thread died "
        f"holding them or a with-block leaked): {held!r}")
