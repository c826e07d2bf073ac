"""stf.Session: run fetches against the graph on TPU.

TPU-native replacement for the reference session stack
(ref: tensorflow/python/client/session.py ``BaseSession.run``,
tensorflow/core/common_runtime/direct_session.cc ``DirectSession::Run``).

Execution model (see framework/lowering.py): the pruned fetch subgraph is
traced into ONE pure function ``step(state, feeds, rng) -> (fetches, state')``
and jitted; XLA compiles/fuses the whole step for the TPU. The Session owns:

- a VariableStore: the single device-resident copy of all variable values
  (jax.Arrays in HBM, with NamedShardings when stf.parallel is in use). The
  full state dict is passed donated into each step so updates are in-place
  in HBM — the role of the reference's BFC-allocated persistent tensors
  (ref: core/common_runtime/bfc_allocator.cc) is played by XLA buffer
  donation.
- an executable cache keyed by (fetch names, feed names); jax.jit adds its
  own retrace keying on feed shapes/dtypes, mirroring the reference's
  executor cache keyed on the rewritten graph
  (ref: direct_session.cc ``GetOrCreateExecutors``).
- a host stage: ops registered ``runs_on_host`` (queues, readers, py_func
  sources, variable introspection) run eagerly in Python before the XLA
  program; their outputs feed the device stage. This replaces the
  reference's CPU-device placement for IO ops
  (ref: core/common_runtime/simple_placer.cc).

Two-level RNG: the session advances a root key every run; random ops fold in
per-op stream ids (framework/random_seed.py) — stateful-RNG API, functional
implementation.
"""

from __future__ import annotations

import os
import sys
import threading
import time
import weakref
from typing import Any, Dict, List, Optional, Sequence, Set, Tuple

import numpy as np

from ..framework import dtypes as dtypes_mod
from ..framework import graph as ops_mod
from ..framework import lowering as lowering_mod
from ..framework import errors
from ..platform import monitoring
from ..platform import sync as _sync
from ..telemetry import recorder as _flight_mod
from ..telemetry import tracing as _req_tracing

Tensor = ops_mod.Tensor
Operation = ops_mod.Operation

_default_session_stack = threading.local()

# every constructed Session, while alive — the telemetry server's
# /statusz reads plan-cache and variable-store summaries from here
live_sessions: "weakref.WeakSet" = weakref.WeakSet()

# -- lifecycle metrics (ref: core/common_runtime metrics in
# core/framework/metrics.cc; see docs/OBSERVABILITY.md for the catalog) ------
_metric_runs = monitoring.Counter(
    "/stf/session/runs", "Session.run calls (all sessions, this process)")
_metric_cache_hits = monitoring.Counter(
    "/stf/session/executable_cache/hits",
    "run() served by an already-planned executable")
_metric_cache_misses = monitoring.Counter(
    "/stf/session/executable_cache/misses",
    "run() that had to plan (and usually jit-compile) a new executable",
    "reason")
_metric_run_seconds = monitoring.Sampler(
    "/stf/session/run_seconds",
    monitoring.ExponentialBuckets(1e-5, 2.0, 30),
    "wall seconds per Session.run")
_metric_await_device_seconds = monitoring.Sampler(
    "/stf/session/await_device_seconds",
    monitoring.ExponentialBuckets(1e-6, 2.0, 30),
    "seconds a run waited for the device results it fetches to be ready "
    "(run_seconds minus this is the host's share of a call)")
_metric_compile_seconds = monitoring.Sampler(
    "/stf/session/jit_compile_seconds",
    monitoring.ExponentialBuckets(1e-3, 2.0, 24),
    "XLA compile seconds per new executable (on untraced first calls the "
    "sample includes the first execution — compile dominates)")
_metric_deadline_exceeded = monitoring.Counter(
    "/stf/session/deadline_exceeded",
    "runs aborted by RunOptions.timeout_in_ms")
# -- device-resident loop + steady-state fast path (docs/PERFORMANCE.md) -----
_metric_fast_path = monitoring.Counter(
    "/stf/session/fast_path_hits",
    "cache-hit runs of a pure device program (no host stages): plan, "
    "analysis, and lint were all skipped")
_metric_fused_steps = monitoring.Counter(
    "/stf/session/fused_steps_amortized",
    "training steps executed inside a fused run_steps device loop "
    "(each window of N steps pays ONE host dispatch)")
_metric_fusion_fallback = monitoring.Counter(
    "/stf/session/loop_fusion_fallbacks",
    "run_steps windows that refused fusion and ran N sequential "
    "Session.run calls instead", "reason")
_metric_fetch_materialize = monitoring.Counter(
    "/stf/session/fetch_materializations",
    "lazy FetchFuture fetches materialized to host numpy (device_get)")

# chrome-trace track per lifecycle phase (Timeline emits thread_name
# metadata for these): 0 = planning, 1 = host stages, 2 = device
_PHASE_TRACK = {"prune": 0, "optimize": 0, "lower": 0,
                "host_stage": 1, "post_host_stage": 1,
                "prepare": 1, "stage_feeds": 1, "commit": 1, "assemble": 1,
                "fetch": 1, "await_device": 1, "copy_to_host": 1,
                "jit_compile": 2, "cost_analysis": 2, "device_execute": 2}
_TRACK_NAMES = {0: "planning", 1: "host", 2: "device"}
# traced run_steps adds a fourth track breaking the fused window down
# by graph op (cost-model attribution; docs/OBSERVABILITY.md)
_ATTRIBUTED_TRACK = 3


def _attributed_device_nodes(step, window_node, min_frac=0.005,
                             top_k=24) -> List[Dict[str, Any]]:
    """Device-time attribution (ISSUE 8 tentpole): child spans breaking
    the ``fused_device_execute`` bar down by graph op. Per-op weights
    are the static cost model's flops+bytes estimates (the accounting
    the bench rows and RunMetadata.cost_graph already use), scaled into
    the MEASURED window duration; plan order is preserved, and ops
    below ``min_frac`` of the total (or beyond the ``top_k`` heaviest)
    merge into "(k small ops)" segments so the track stays readable."""
    from ..framework import cost_model

    ops = step.device_ops
    weights: List[float] = []
    total = 0.0
    for op in ops:
        try:
            w = float(cost_model._op_flops(op)
                      + cost_model._op_bytes_dispatch(op))
        except Exception:  # noqa: BLE001 — attribution is best-effort
            w = 0.0
        weights.append(w)
        total += w
    if total <= 0:
        return []
    heavy = set(sorted(range(len(ops)),
                       key=lambda i: -weights[i])[:top_k])
    nodes: List[Dict[str, Any]] = []
    start, dur = window_node["start_us"], window_node["dur_us"]
    cursor = start
    pend_w, pend_n = 0.0, 0

    def _flush():
        nonlocal cursor, pend_w, pend_n
        if pend_n:
            d = dur * pend_w / total
            nodes.append({"name": f"({pend_n} small ops)",
                          "start_us": cursor, "dur_us": max(d, 0.1),
                          "tid": _ATTRIBUTED_TRACK,
                          "args": {"frac": f"{pend_w / total:.4f}"}})
            cursor += d
            pend_w, pend_n = 0.0, 0

    for i, op in enumerate(ops):
        if i in heavy and weights[i] >= min_frac * total:
            _flush()
            d = dur * weights[i] / total
            nodes.append({"name": f"{op.type}:{op.name}",
                          "start_us": cursor, "dur_us": max(d, 0.1),
                          "tid": _ATTRIBUTED_TRACK,
                          "args": {"frac": f"{weights[i] / total:.4f}",
                                   "op_type": op.type}})
            cursor += d
        else:
            pend_w += weights[i]
            pend_n += 1
    _flush()
    return nodes


def _drain_spans_to_nodes(buf: "monitoring.TraceBuffer",
                          base_s: float) -> List[Dict[str, Any]]:
    """Traced-run span buffer -> step_stats ``nodes`` (chrome-trace
    rows). Shared by ``run`` and the fused ``run_steps`` path."""
    nodes: List[Dict[str, Any]] = []
    for span in sorted(buf.drain(), key=lambda s: s["start_s"]):
        phase = span["name"].split(":")[0]
        node = {
            "name": span["name"],
            "start_us": (span["start_s"] - base_s) * 1e6,
            "dur_us": max(span["dur_s"] * 1e6, 1.0),
            "tid": _PHASE_TRACK.get(phase, 0),
        }
        if span.get("meta"):
            node["args"] = {k: str(v) for k, v in span["meta"].items()}
        nodes.append(node)
    return nodes


def _check_deadline(deadline, what):
    if deadline is not None and time.perf_counter() > deadline:
        _metric_deadline_exceeded.get_cell().increase_by(1)
        raise errors.DeadlineExceededError(
            None, None,
            f"Session.run exceeded RunOptions.timeout_in_ms after {what}")


# at most this many timed-out waiter threads may be outstanding at once:
# each one blocks in block_until_ready pinning its attempt's device
# buffers, so a retry loop against a wedged device must not grow them
# without bound
_deadline_waiters = threading.BoundedSemaphore(8)


def _block_with_deadline(values, deadline):
    """Block until device results are ready; with a deadline, wait in a
    helper thread so the deadline can fire mid-wait. Detection only — XLA
    execution is not cancelled, and the caller commits variable state
    BEFORE this wait so a timeout never leaves donated (deleted) buffers
    in the store."""
    import jax

    if deadline is None:
        jax.block_until_ready(values)
        return
    remaining = deadline - time.perf_counter()
    if remaining > 0:
        if not _deadline_waiters.acquire(blocking=False):
            # waiter pool exhausted (many concurrent timed waits, or
            # earlier timeouts against a wedged device still pinned):
            # degrade to an unenforced wait — never report a timeout
            # whose budget did not actually elapse
            jax.block_until_ready(values)
            return
        done = threading.Event()
        err: List[BaseException] = []

        def _wait():
            try:
                jax.block_until_ready(values)
            except BaseException as e:  # surfaced on the caller thread
                err.append(e)
            finally:
                done.set()
                _deadline_waiters.release()

        th = threading.Thread(target=_wait, daemon=True,
                              name="stf_session_deadline_wait")
        th.start()
        if done.wait(remaining):
            if err:
                # an async XLA/runtime failure must raise exactly like
                # the no-deadline path would at its block_until_ready
                raise err[0]
            return
    _metric_deadline_exceeded.get_cell().increase_by(1)
    raise errors.DeadlineExceededError(
        None, None,
        "Session.run exceeded RunOptions.timeout_in_ms waiting for "
        "device results (execution continues; session state stays "
        "consistent)")


def _call_step_executable(step, state, feed_args, rng_key, rng_ctr):
    """Run the step's device program: a per-feed-shape AOT executable
    from ``step.aot_cache`` (ExecutionPlan.compile fills it — the
    serving path keeps one executable warm per batch bucket), else the
    pinned single-slot AOT executable, else the jit path. A stale
    executable is dropped — along with its now-stale cost analysis —
    when the avals changed (the AOT call rejects new shapes/dtypes with
    TypeError before executing, so no buffers are donated on the failed
    attempt)."""
    sig = None
    exe = None
    if step.aot_cache:
        from ..compiler import aot

        sig = aot.feed_signature(feed_args)
        exe = step.aot_cache.get(sig)
    if exe is None:
        exe = step.compiled if step.compiled is not None else step.jitted
    try:
        return exe(dict(state), feed_args, rng_key, rng_ctr)
    except TypeError:
        if exe is step.jitted:
            raise
        from ..telemetry import memory as _memory_mod

        if exe is step.compiled:
            step.compiled = None
            step.xla_cost = None
            _memory_mod.get_ledger().release(step.compiled_mem_token)
            step.compiled_mem_token = None
        elif sig is not None:
            # bucket executable compiled against older state avals
            # (e.g. variables re-initialized with a new dtype)
            stale = step.aot_cache.pop(sig, None)
            if stale is not None:
                _memory_mod.get_ledger().release(
                    getattr(stale, "mem_token", None))
        return step.jitted(dict(state), feed_args, rng_key, rng_ctr)


def _plan_uses_rng(ops, _depth=0) -> bool:
    """Whether any op in the plan declares an RNG effect, recursing into
    FuncGraph bodies (cond branches, while/scan bodies). Conservative:
    anything unresolvable counts as RNG-consuming. Plans with no RNG
    consumer do not advance the session's run counter (see
    ``_rng_args``), which is what keeps a checkpoint-resumed RNG stream
    aligned with the uninterrupted run no matter how many read-only
    runs (hook setup, ready checks) the restore path issued."""
    from ..analysis import effects as effects_mod
    from ..framework import optimizer as optimizer_mod

    for op in ops:
        try:
            if effects_mod.op_effects(op).rng:
                return True
        except Exception:  # noqa: BLE001 — unknown op: consume
            return True
        spec = optimizer_mod.function_op_spec(op.type)
        if spec is None:
            continue
        if _depth >= 8:
            return True  # pathological nesting: stay conservative
        try:
            descs = spec.bodies(op.attrs, len(op.inputs))
            bodies = [op.attrs.get(d["attr"]) for d in descs]
        except Exception:  # noqa: BLE001
            return True
        for fg in bodies:
            if fg is None:
                continue
            try:
                body_ops = fg.get_operations()
            except Exception:  # noqa: BLE001
                return True
            if _plan_uses_rng(body_ops, _depth + 1):
                return True
    return False


def _executable_analysis(lowered, compiled):
    """flops/bytes (XLA cost_analysis) + memory stats (memory_analysis,
    needs a compiled executable) in the RunMetadata.cost_graph shape.
    Best-effort: backends may expose neither. Normalization lives in
    utils/perf (cost_of / memory_of) — one place tracks jax's API."""
    from ..utils import perf

    out: Dict[str, Any] = {}
    cost = perf.cost_of(compiled if compiled is not None else lowered)
    if cost:
        out["flops"] = cost["flops"]
        out["bytes_accessed"] = cost["bytes"]
    if compiled is not None:
        mem = perf.memory_of(compiled)
        if mem:
            out["memory"] = mem
        coll = perf.collective_bytes_of(compiled)
        if coll:
            out["collective_bytes"] = coll
    return out


class FetchFuture:
    """Lazy handle for a device-produced fetch (ConfigProto(
    async_fetches=True), docs/PERFORMANCE.md).

    ``Session.run`` returns these instead of eager numpy so the run call
    only *dispatches* the step: the device_get happens at first host
    access (``np.asarray``/``float``/``int``/``.result()``), letting the
    caller stage step N+1's feeds while step N still executes. An async
    XLA/runtime failure therefore surfaces at materialization, not at
    the run call that dispatched it. Thread-safe: concurrent
    materializations resolve the same immutable device value; the
    ``/stf/session/fetch_materializations`` counter ticks once."""

    __slots__ = ("_device_value", "_host_value", "_lock")

    def __init__(self, device_value):
        self._device_value = device_value
        self._host_value = None
        self._lock = _sync.Lock("session/fetch_future",
                                rank=_sync.RANK_STATE)

    @property
    def materialized(self) -> bool:
        return self._device_value is None

    def device_value(self):
        """The underlying jax.Array (no host transfer), or None once
        materialized."""
        return self._device_value

    def result(self):
        """Materialize: block on the device value and return host numpy
        (device errors raise here)."""
        with self._lock:
            if self._device_value is not None:
                value = np.asarray(self._device_value)
                self._host_value = value
                self._device_value = None
                _metric_fetch_materialize.get_cell().increase_by(1)
        return self._host_value

    # numpy/python interop: any host access materializes
    def __array__(self, dtype=None, copy=None):
        out = self.result()
        return out.astype(dtype) if dtype is not None else out

    def __float__(self):
        return float(self.result())

    def __int__(self):
        return int(self.result())

    def __bool__(self):
        return bool(self.result())

    def __index__(self):
        return int(self.result())

    def _peek(self):
        # single read of each slot: a concurrent result() may flip the
        # pair between reads, but the snapshot stays a valid value
        v = self._device_value
        return v if v is not None else self._host_value

    @property
    def shape(self):
        return self._peek().shape

    @property
    def dtype(self):
        return self._peek().dtype

    def __repr__(self):
        state = "materialized" if self.materialized else "pending"
        return f"<FetchFuture {state} shape={tuple(self.shape)} " \
               f"dtype={self.dtype}>"


def _await_device(values):
    """One explicit wait for device ``values``, the one ``np.asarray``
    would make, so that the wait and the copy are told apart — traced or
    not: ``session/await_device``, sampled by
    ``/stf/session/await_device_seconds``."""
    with monitoring.traceme("session/await_device"):
        t0 = time.perf_counter()
        _block_with_deadline(values, None)
        waited = time.perf_counter() - t0
    _metric_await_device_seconds.get_cell().add(waited)


def materialize(fetched: Dict[Any, Any]) -> Dict[Any, Any]:
    """Host values of a run's fetches ``{key: value}`` whose device values
    came back as :class:`FetchFuture` (``ExecutionPlan.execute(...,
    as_futures=True)``): one wait for all of them, then their copies,
    under ``session/fetch`` — what the run that enqueued them does when it
    fetches at once. A device error of that run raises here."""
    with monitoring.traceme("session/fetch"):
        pending = [f.device_value() for f in fetched.values()
                   if isinstance(f, FetchFuture) and not f.materialized]
        if pending:
            _await_device(pending)
        with monitoring.traceme("session/copy_to_host"):
            return {k: f.result() if isinstance(f, FetchFuture) else f
                    for k, f in fetched.items()}


def get_default_session():
    stack = getattr(_default_session_stack, "stack", None)
    return stack[-1] if stack else None


_store_counter = [0]


def _release_ledger_tokens(tokens: Dict[str, int]):
    """weakref.finalize callback for a dropped (never-closed) store:
    whatever entries remain release so the ledger never leaks a dead
    session's accounting. Must not capture the store itself (and must
    never raise — finalizers can run at interpreter shutdown)."""
    try:
        from ..telemetry import memory as _memory_mod

        ledger = _memory_mod.get_ledger()
        for token in tokens.values():
            ledger.release(token)
        tokens.clear()
    except Exception:  # noqa: BLE001 — accounting only
        pass


def _device_nbytes(arr) -> int:
    """PER-DEVICE bytes of a (possibly sharded) array — what one chip's
    HBM actually holds. The ledger (and therefore
    ``device_memory_budget_bytes`` admission) accounts this, so a
    head-sharded tp=8 KV cache costs 1/8 of its replicated footprint:
    a model whose replicated cache busts the budget can still load at
    tp=8. Replicated/unsharded arrays fall back to the logical size."""
    nbytes = int(getattr(arr, "nbytes", 0))
    sh = getattr(arr, "sharding", None)
    if sh is None or not nbytes:
        return nbytes
    try:
        if getattr(arr, "is_fully_replicated", True):
            return nbytes
        shard_shape = sh.shard_shape(arr.shape)
        n = 1
        for d in shard_shape:
            n *= int(d)
        full = 1
        for d in arr.shape:
            full *= int(d)
        if full:
            return max(int(nbytes * n // full), 1)
    except Exception:  # noqa: BLE001 — accounting only
        pass
    return nbytes


class VariableStore:
    """Device-resident variable state: name -> jax.Array.

    Every entry is accounted in the process HBM ledger
    (stf.telemetry.memory): ``sync_ledger`` reconciles the ledger with
    the store's key set — called after each state commit, it is a
    two-comparison no-op while the key set is unchanged (the
    steady-state training loop). Classification (weights / optimizer
    slots / kv_cache / state) comes from ``classes`` hints (KV-cache
    allocs register theirs at trace time) and the owning session's
    classifier over the graph's variable registry."""

    def __init__(self, owner: Optional[str] = None):
        self.values: Dict[str, Any] = {}
        self.shardings: Dict[str, Any] = {}
        # ledger class hints by store name (e.g. "kv_cache", set by
        # ops/kv_cache_ops at trace time); the classifier covers the rest
        self.classes: Dict[str, str] = {}
        self._classifier = None  # name -> ledger class (set by Session)
        if owner is None:
            _store_counter[0] += 1
            owner = f"session-{_store_counter[0]}"
        self.owner = owner
        self._ledger_keys: frozenset = frozenset()
        self._ledger_tokens: Dict[str, int] = {}
        weakref.finalize(self, _release_ledger_tokens,
                         self._ledger_tokens)

    def sync_ledger(self):
        """Reconcile ledger entries with the store's key set. Fast path
        (unchanged keys — every steady-state step) is one dict-view
        comparison; donation swaps array identities but never sizes."""
        vals = self.values
        if vals.keys() == self._ledger_keys:
            return
        from ..telemetry import memory as _memory_mod

        ledger = _memory_mod.get_ledger()
        keys = frozenset(vals)
        for name in self._ledger_keys - keys:
            ledger.release(self._ledger_tokens.pop(name, None))
        for name in keys - self._ledger_keys:
            arr = vals[name]
            cls = self.classes.get(name)
            if cls is None and self._classifier is not None:
                try:
                    cls = self._classifier(name)
                except Exception:  # noqa: BLE001 — accounting only
                    cls = None
            # arrays=None: store attribution for reconcile() comes
            # from the live_sessions sweep (one pass over each store),
            # not per-entry refs — V entries each walking the V-array
            # store would make reconcile O(V^2)
            self._ledger_tokens[name] = ledger.register(
                name, _device_nbytes(arr),
                cls or _memory_mod.CLASS_STATE, self.owner)
        self._ledger_keys = keys

    def set_owner(self, owner: str):
        """Re-label this store's ledger entries (ModelServer tags each
        servable's store ``model:<name>`` after load)."""
        from ..telemetry import memory as _memory_mod

        self.owner = owner
        ledger = _memory_mod.get_ledger()
        for token in self._ledger_tokens.values():
            ledger.release(token)
        self._ledger_tokens.clear()
        self._ledger_keys = frozenset()
        self.sync_ledger()

    def release_ledger(self):
        """Drop every ledger entry (Session.close)."""
        _release_ledger_tokens(self._ledger_tokens)
        self._ledger_keys = frozenset()

    def ledger_bytes(self) -> int:
        from ..telemetry import memory as _memory_mod

        return _memory_mod.get_ledger().live_bytes(owner=self.owner)

    def load(self, name: str, value, variable=None):
        import jax
        import jax.numpy as jnp

        dtype = None
        if variable is not None:
            # x64 off: jnp would silently truncate 64-bit dtypes with a
            # warning. Narrow explicitly (single policy:
            # dtypes.narrowed_if_no_x64) so the stored array — and the
            # dtype recorded in checkpoints — is the truth.
            decl = variable.dtype.base_dtype
            dtype = dtypes_mod.narrowed_if_no_x64(decl).np_dtype
            if dtype != decl.np_dtype:
                dtypes_mod.warn_64bit_narrowing_once(f"variable {name!r}")
        if isinstance(value, jax.Array):
            # already on a device: cast there, no trip through the host
            arr = value if dtype is None else value.astype(dtype)
        else:
            arr = jnp.asarray(np.asarray(value), dtype=dtype)
        sh = self.shardings.get(name)
        if sh is None and variable is not None \
                and getattr(variable, "sharding", None) is not None:
            # checkpoint restore of sharded state: the store has not
            # committed this name yet (restore runs before any plan),
            # so honor the variable's DECLARED spec under the active
            # mesh — and register it, so later loads re-place the same
            # way (the sharded-cache/TP-weights restore contract)
            from ..parallel.mesh import current_mesh

            mesh = current_mesh()
            if mesh is not None:
                try:
                    sh = mesh.named_sharding(*variable.sharding)
                    self.shardings[name] = sh
                except Exception:  # noqa: BLE001 — placement hint only
                    sh = None
        if sh is not None:
            arr = jax.device_put(arr, sh)
        self.values[name] = arr
        token = self._ledger_tokens.get(name)
        if token is not None:  # host re-load may resize/re-dtype
            from ..telemetry import memory as _memory_mod

            _memory_mod.get_ledger().update(
                token, _device_nbytes(arr))
        else:
            self.sync_ledger()

    def as_numpy(self, name: str):
        return np.asarray(self.values[name])


def _is_host_device(device_str) -> bool:
    """``with stf.device('/cpu:0')`` pins an op to the host stage (the
    reference's simple_placer CPU assignment,
    core/common_runtime/simple_placer.cc). TPU/GPU/empty scopes keep the op
    in the compiled XLA step; task/job parts are placement-neutral on a
    single host."""
    if not device_str:
        return False
    return "cpu" in str(device_str).lower()


class RunOptions:
    """(ref: config.proto ``RunOptions``). trace_level >= SOFTWARE_TRACE
    makes Session.run block on device results and record per-phase
    lifecycle spans (prune/optimize/lower/jit_compile/device_execute/
    host stages) into the provided RunMetadata's step_stats.
    ``timeout_in_ms > 0`` bounds the run's blocking waits: exceeding it
    raises errors.DeadlineExceededError (detection, not cancellation —
    variable state stays consistent)."""

    NO_TRACE = 0
    SOFTWARE_TRACE = 1
    HARDWARE_TRACE = 2
    FULL_TRACE = 3

    def __init__(self, trace_level=NO_TRACE, timeout_in_ms=0,
                 inter_op_thread_pool=0, output_partition_graphs=False,
                 debug_options=None):
        self.trace_level = trace_level
        self.timeout_in_ms = timeout_in_ms
        self.inter_op_thread_pool = inter_op_thread_pool
        self.output_partition_graphs = output_partition_graphs
        self.debug_options = debug_options


class RunMetadata:
    """(ref: config.proto ``RunMetadata``, core/common_runtime/
    step_stats_collector.cc). ``step_stats`` is the dict client/timeline.py
    renders: {"start_us", "wall_time_s", "nodes": [{name, start_us, dur_us,
    tid}], ...}."""

    def __init__(self):
        self.step_stats: Dict[str, Any] = {}
        self.partition_graphs: List[Any] = []
        self.cost_graph: Dict[str, Any] = {}


class _FetchMapper:
    """Handles nested fetch structures (lists/tuples/dicts/namedtuples) like
    the reference's FetchMapper (ref: python/client/session.py:182)."""

    def __init__(self, graph, fetches):
        self.elements: List[Any] = []  # unique graph elements (Tensor/Operation)
        self._index: Dict[Any, int] = {}
        self.structure = self._build(graph, fetches)

    def _register(self, el):
        if el not in self._index:
            self._index[el] = len(self.elements)
            self.elements.append(el)
        return self._index[el]

    def _build(self, g, f):
        if isinstance(f, (list, tuple)) and not isinstance(f, str):
            kids = [self._build(g, x) for x in f]
            if hasattr(f, "_fields"):  # namedtuple
                return ("namedtuple", type(f), kids)
            return ("list", type(f), kids)
        if isinstance(f, dict):
            return ("dict", type(f),
                    [(k, self._build(g, v)) for k, v in f.items()])
        from ..framework.indexed_slices import IndexedSlices
        from ..framework.sparse_tensor import SparseTensor

        if isinstance(f, IndexedSlices):
            vals = self._build(g, f.values)
            idx = self._build(g, f.indices)
            return ("islices", None, [vals, idx])
        if isinstance(f, SparseTensor):
            return ("sparse", None, [self._build(g, f.indices),
                                     self._build(g, f.values),
                                     self._build(g, f.dense_shape)])
        el = g.as_graph_element(f, allow_tensor=True, allow_operation=True)
        return ("leaf", None, self._register(el))

    def rebuild(self, values, node=None):
        node = node or self.structure
        kind, typ, payload = node
        if kind == "leaf":
            return values[payload]
        if kind == "dict":
            return typ((k, self.rebuild(values, v)) for k, v in payload)
        if kind == "islices":
            from ..framework.indexed_slices import IndexedSlices

            return IndexedSlices(self.rebuild(values, payload[0]),
                                 self.rebuild(values, payload[1]))
        if kind == "sparse":
            from ..framework.sparse_tensor import SparseTensorValue

            return SparseTensorValue(self.rebuild(values, payload[0]),
                                     self.rebuild(values, payload[1]),
                                     self.rebuild(values, payload[2]))
        kids = [self.rebuild(values, k) for k in payload]
        if kind == "namedtuple":
            return typ(*kids)
        if typ is tuple:
            return tuple(kids)
        return kids


class _CompiledStep:
    __slots__ = ("jitted", "device_fetches", "host_plan", "post_host_plan",
                 "post_host_inputs", "device_ops", "feed_tensors", "boundary",
                 "has_device_stage", "n_calls", "last_lowering_ctx",
                 "check_msgs", "const_env", "alias", "fetch_nbytes",
                 "raw_post_inputs", "func_plans", "compiled", "xla_cost",
                 "feed_shardings", "fused", "fusion_diags",
                 "sharding_report", "sharding_thread",
                 "sharding_sync_seconds", "sharding_gate", "aot_cache",
                 "uses_rng", "memory_estimate", "compiled_mem_token",
                 "numerics")

    def __init__(self):
        self.n_calls = 0
        self.last_lowering_ctx = None
        self.post_host_plan = []
        self.post_host_inputs = []
        self.const_env = {}
        self.alias = {}
        self.fetch_nbytes = []
        self.raw_post_inputs = set()
        self.func_plans = {}
        # AOT-compiled executable + its XLA cost/memory analysis: filled
        # on traced first calls (jit_compile phase); ``compiled`` serves
        # later same-shape calls, falling back to ``jitted`` on aval
        # mismatch. xla_cost None = never tried, {} = tried, unavailable.
        self.compiled = None
        self.xla_cost = None
        # per-plan memory accounting (stf.telemetry.memory): the cost
        # model's predicted peak/resident bytes — computed eagerly when
        # a device-memory budget gates admission, lazily by
        # ExecutionPlan.memory_info() otherwise
        self.memory_estimate = None
        # HBM-ledger token of the traced-path AOT executable (class
        # "executable"; released when the executable is dropped)
        self.compiled_mem_token = None
        # steady-state staging slots (_staged_feed): tensor name -> its
        # sharding annotation (None = plain feed), plus per-mesh
        # committed NamedShardings under (name, "ns") keys
        self.feed_shardings = {}
        # (n, output_mode, xs-name-set) -> fused N-step executable
        self.fused = {}
        # feed-shape signature -> AOT executable (compiler.aot
        # feed_signature keys): ExecutionPlan.compile pre-compiles one
        # per serving batch bucket so the first request of each bucket
        # shape never pays a trace+compile. Empty on training plans —
        # the hot path pays one truthiness check.
        self.aot_cache = {}
        # stf.analysis.sharding per-plan report (mesh active at plan
        # time): predicted collective bytes + lint findings, surfaced
        # through RunMetadata.cost_graph["predicted_collectives"].
        # Computed on a worker thread overlapping lowering/XLA compile
        # (the analysis is advisory — warnings, never a gate — so it
        # stays off the plan's critical path); join_sharding() waits.
        self.sharding_report = None
        self.sharding_thread = None
        self.sharding_gate = None
        self.sharding_sync_seconds = 0.0
        # cached loop-safety certification: None = not yet checked,
        # else (plan-static diagnostics, assigned-variable names) — the
        # store-dependent uninitialized-write check re-runs per call
        self.fusion_diags = None
        # whether any device op (recursing into FuncGraph bodies)
        # declares an RNG effect: only such plans advance the session's
        # RNG run counter, so incidental read-only runs — hook setup,
        # `report_uninitialized_variables` on the restore path — can
        # never shift the key stream a checkpoint resume must reproduce
        # bit-exactly (stf.checkpoint; docs/CHECKPOINT.md)
        self.uses_rng = True
        # numerics-health plane (stf.debug.numerics): when the plan was
        # auto-instrumented, {"mode", "taps", "tensor", "index"} — the
        # packed [T, 4] health tensor rides device_fetches[index] (and
        # the fused-window ys) at near-zero cost; None = plane off or
        # plan not training-shaped
        self.numerics = None

    def join_sharding(self, timeout=10.0):
        """Wait for the overlapped sharding analysis (if any) and return
        the report (None when it did not run or has not finished)."""
        th = self.sharding_thread
        if th is not None:
            if self.sharding_gate is not None:
                self.sharding_gate.set()  # don't wait out the head start
            th.join(timeout)
            if not th.is_alive():
                self.sharding_thread = None
        return self.sharding_report


class ExecutionPlan:
    """The explicit PLAN half of ``Session.run``, as a first-class handle
    (ref: the reference's ``GetOrCreateExecutors`` + ``_Callable`` pair,
    core/common_runtime/direct_session.cc).

    ``Session.plan(fetches, feeds)`` resolves the fetch structure and
    plans (prune/optimize/analyze/lower) exactly once; ``execute``
    then only stages feeds, dispatches the device program, and
    assembles results. ``stf.serving.ModelServer`` drives these two
    layers directly — one plan per (model, signature), one execute per
    coalesced batch — so training and serving share a single executor
    path instead of a serving-only runtime.

    ``compile`` AOT-compiles the plan's device program for one concrete
    feed-shape bucket ahead of traffic (compiler.aot.AotStepExecutable);
    executions whose feed shapes match a compiled bucket skip the jit
    retrace entirely. Thread-safety matches Session.run: concurrent
    executes serialize their device stage on the session lock.
    """

    def __init__(self, session, mapper, feed_tensors, step, key):
        self._session = session
        self._mapper = mapper
        self._step = step
        self._key = key
        self.feed_tensors: List[Tensor] = list(feed_tensors)
        self._planned_set = frozenset(self.feed_tensors)

    @property
    def session(self):
        return self._session

    @property
    def step(self) -> "_CompiledStep":
        """The planned step (advanced introspection; owned by the
        session's executable cache)."""
        return self._step

    @property
    def has_host_stages(self) -> bool:
        """Whether executions run Python host stages around the device
        program (serving plans should be pure device: the serving lint
        flags the offending ops)."""
        return bool(self._step.host_plan or self._step.post_host_plan)

    @property
    def device_op_count(self) -> int:
        return len(self._step.device_ops) if self._step.has_device_stage \
            else 0

    def compiled_buckets(self) -> List[Any]:
        """Feed-shape signatures with a warm AOT executable."""
        return sorted(self._step.aot_cache)

    def memory_info(self) -> Dict[str, Any]:
        """Per-plan memory accounting (ISSUE 13, docs/OBSERVABILITY.md
        "Device memory"): the static cost model's predicted peak /
        resident / transient bytes for this plan, the XLA
        ``memory_analysis`` of a compiled executable when one exists
        (traced first call or an AOT bucket), and the HBM ledger's
        measured live set — prediction next to measurement."""
        sess = self._session
        step = self._step
        if step.memory_estimate is None:
            step.memory_estimate = sess._estimate_plan_memory(
                self._mapper.elements, self.feed_tensors)
        out = dict(step.memory_estimate)
        xla_mem = (step.xla_cost or {}).get("memory") \
            if step.xla_cost else None
        if not xla_mem and step.aot_cache:
            from ..utils import perf

            exe = next(iter(step.aot_cache.values()))
            xla_mem = perf.memory_of(exe._compiled,
                                     lowered=exe._lowered) or None
        if xla_mem:
            out["xla_memory"] = dict(xla_mem)
        from ..telemetry import memory as _memory_mod

        led = _memory_mod.get_ledger()
        out["ledger_live_bytes"] = led.total_bytes()
        out["ledger_session_bytes"] = led.live_bytes(
            owner=sess._variable_store.owner)
        out["budget_bytes"] = sess._memory_budget or None
        return out

    def compile(self, feed_shapes=None):
        """AOT-compile the plan's device program for one feed-shape
        bucket and pin it in the step's executable cache.

        ``feed_shapes``: {tensor_or_name: concrete shape} overriding the
        planned placeholder shapes (typically just the batch dim:
        ``{x: (bucket, 784)}``). Feeds not listed must already have
        fully static shapes. Variable avals come from the session's
        CURRENT variable store — initialize/restore variables first.
        Returns the :class:`~..compiler.aot.AotStepExecutable`.
        """
        from ..compiler import aot

        sess = self._session
        step = self._step
        if not step.has_device_stage:
            raise errors.InvalidArgumentError(
                None, None,
                "ExecutionPlan.compile: the plan has no device stage "
                "(host-only or constant-folded fetches) — nothing to "
                "AOT-compile")
        shapes: Dict[Tensor, Tuple[int, ...]] = {}
        for k, shp in (feed_shapes or {}).items():
            t = sess._graph.as_graph_element(k, allow_tensor=True,
                                             allow_operation=False)
            shapes[t] = tuple(int(d) for d in shp)
        import jax

        avals: Dict[str, Any] = {}
        for t in step.feed_tensors:
            shp = shapes.get(t)
            if shp is None:
                if t.shape.rank is None or \
                        any(d is None for d in t.shape.as_list()):
                    raise ValueError(
                        f"AOT feed {t.name} has dynamic shape {t.shape}; "
                        "pass its concrete bucket shape via feed_shapes")
                shp = tuple(t.shape.as_list())
            elif not t.shape.is_compatible_with(shp):
                raise ValueError(
                    f"AOT feed shape {shp} incompatible with tensor "
                    f"{t.name} shape {t.shape}")
            np_dtype = dtypes_mod.narrowed_if_no_x64(
                t.dtype.base_dtype).np_dtype
            # the sharding the feed will be staged with: an AOT
            # executable rejects arguments sharded otherwise
            avals[t.name] = jax.ShapeDtypeStruct(
                shp, np_dtype, sharding=sess._feed_sharding(step, t))
        with sess._lock:
            rng_key = sess._ensure_base_key()
            state = dict(sess._variable_store.values)
        t0 = time.perf_counter()
        with monitoring.traceme("session/aot_compile", n_feeds=len(avals)):
            exe = aot.compile_step(step.jitted, state, avals, rng_key,
                                   np.uint32(0))
        _metric_compile_seconds.get_cell().add(time.perf_counter() - t0)
        # HBM ledger + budget admission (stf.telemetry.memory): the
        # compile-time memory_analysis gates admission when the session
        # carries a budget — a bucket whose transient footprint cannot
        # fit is refused HERE, before any request OOMs mid-batch — and
        # the executable's code buffer then registers as class
        # "executable" (admission first: the not-yet-registered code
        # bytes ride requested_bytes exactly once)
        from ..telemetry import memory as _memory_mod
        from ..utils import perf as _perf

        mem = _perf.memory_of(exe._compiled, lowered=exe._lowered)
        code_bytes = int(mem.get("generated_code_bytes", 0)) if mem \
            else 0
        if sess._memory_budget and mem:
            transient = (mem.get("temp_bytes", 0)
                         + mem.get("output_bytes", 0)
                         - mem.get("alias_bytes", 0))
            _memory_mod.check_budget(
                sess._memory_budget, max(0, transient) + code_bytes,
                "compile", owner=sess._variable_store.owner,
                detail=f"AOT bucket memory_analysis: {mem}")
        exe.mem_token = _memory_mod.get_ledger().register(
            f"aot:{exe.cache_key}", code_bytes,
            _memory_mod.CLASS_EXECUTABLE, sess._variable_store.owner)
        # a recompile of the same bucket replaces the cached
        # executable: release the predecessor's ledger entry or its
        # code bytes leak as phantom live set
        prev = step.aot_cache.get(exe.feed_signature)
        if prev is not None:
            _memory_mod.get_ledger().release(
                getattr(prev, "mem_token", None))
        step.aot_cache[exe.feed_signature] = exe
        return exe

    def execute(self, feed_dict=None, options=None, as_futures=None):
        """Run one planned step: stage feeds, dispatch, assemble — no
        fetch mapping, no cache lookup, no re-plan.

        ``options.timeout_in_ms`` bounds the blocking waits exactly like
        ``Session.run`` (commit-then-detect DeadlineExceededError).
        ``as_futures=True`` returns device-produced fetches as lazy
        :class:`FetchFuture` handles regardless of
        ConfigProto(async_fetches) — the serving batcher's response
        path. Traced runs (RunMetadata) stay on ``Session.run``.
        """
        sess = self._session
        if sess._closed:
            raise RuntimeError("Attempted to use a closed Session.")
        t0 = time.perf_counter()
        _metric_runs.get_cell().increase_by(1)
        timeout_ms = (int(getattr(options, "timeout_in_ms", 0) or 0)
                      if options is not None else 0)
        deadline = t0 + timeout_ms / 1000.0 if timeout_ms > 0 else None
        feeds = sess._normalize_feeds(feed_dict)
        planned = self._planned_set
        if feeds.keys() != planned:
            missing = sorted(t.name for t in planned - set(feeds))
            extra = sorted(t.name for t in set(feeds) - planned)
            raise errors.InvalidArgumentError(
                None, None,
                "ExecutionPlan.execute: feeds must match the planned "
                f"signature (missing: {missing}, unplanned: {extra}); "
                "build a new plan for a different feed set")
        # request-scoped tracing: inside a serving batch's trace scope
        # the span also links the executor dispatch to the riding
        # requests, as the ring's ``plan_execute``
        with (_req_tracing.span("session/run", ring="plan_execute",
                                n_feeds=len(feeds))
              if _req_tracing.current_trace_ids() is not None
              else monitoring.traceme("session/run")):
            values = sess._execute_plan(self._step, self._mapper.elements,
                                        feeds, deadline=deadline,
                                        async_fetches=as_futures)
            with monitoring.traceme("session/assemble"):
                out = self._mapper.rebuild(values)
        _metric_run_seconds.get_cell().add(time.perf_counter() - t0)
        return out

    __call__ = execute


class BaseSession:
    def __init__(self, target="", graph=None, config=None):
        self._target = self._resolve_target(target)
        self._graph = graph or ops_mod.get_default_graph()
        self._config = config
        # stf.analysis wiring (ISSUE 3): construction-time strict/warn
        # verification; per-plan checks run in _plan (cached by plan
        # signature — a plan is analyzed exactly once per executable)
        self._analysis_mode = getattr(config, "graph_analysis", "off") \
            if config is not None else "off"
        if self._analysis_mode != "off":
            self._verify_graph_now(construction=True)
        # persistent executable cache (ISSUE 5): ConfigProto(
        # compile_cache_dir=...) or STF_COMPILE_CACHE makes process
        # restarts disk-hit their compiles instead of paying them again.
        # The jax cache dir is PROCESS-GLOBAL (see ConfigProto doc):
        # once set it outlives this Session and applies to later ones.
        # Where JAX_COMPILATION_CACHE_DIR is set it wins over both
        # (compiler/aot.py holds the one rule).
        cache_dir = (getattr(config, "compile_cache_dir", None)
                     if config is not None else None) \
            or os.environ.get("STF_COMPILE_CACHE")
        if cache_dir:
            from ..compiler import aot

            aot.enable_persistent_cache(cache_dir)
        # telemetry plane (ISSUE 8): ConfigProto(telemetry_port=...)
        # starts the process's HTTP server (/metrics /healthz /statusz
        # /tracez /flightz). PROCESS-GLOBAL like the compile cache: the
        # server outlives this Session.
        telemetry_port = getattr(config, "telemetry_port", None) \
            if config is not None else None
        if telemetry_port is not None:
            from .. import telemetry

            telemetry.start(port=telemetry_port)
        self._guard_warned: Set[str] = set()
        self._fusion_warned: Set[Any] = set()
        self._variable_store = VariableStore()
        self._variable_store._classifier = self._classify_var
        # device-memory budget (stf.telemetry.memory; ISSUE 13): plans,
        # AOT compiles, and servable loads against this session are
        # admission-checked against the process HBM ledger — a program
        # that cannot fit is refused with ResourceExhaustedError (and a
        # forensic ledger dump) BEFORE launch. None = unlimited.
        self._memory_budget = int(getattr(
            config, "device_memory_budget_bytes", 0) or 0) \
            if config is not None else 0
        self._cache: Dict[Any, _CompiledStep] = {}
        # (fetch, feed) signature -> rewrite_version at last plan:
        # classifies executable-cache miss reasons
        self._sig_versions: Dict[Any, int] = {}
        self._closed = False
        self._run_counter = 0
        # blocking_ok: Session.run() executes device programs and
        # fetches results under this reentrant lock by design — run
        # calls are serialized per session (reference semantics), so
        # the device wait IS the critical section, not a convoy.
        self._lock = _sync.RLock("client/session",
                                 rank=_sync.RANK_SESSION,
                                 blocking_ok=True)
        self._host_rng = np.random.RandomState(
            self._graph.seed if self._graph.seed is not None else 12345)
        self._base_key = None  # created lazily (jax import cost)
        self._resources: Dict[str, Any] = {}  # queues, readers, tables
        self._partial_runs: Dict[str, Any] = {}
        # device-resident tensors pinned by get_session_handle
        # (ref: python/ops/session_ops.py; TPU-native: values are
        # jax.Arrays that never round-trip through host numpy)
        self._handles: Dict[str, Any] = {}
        self._handle_counter = 0
        # flight-recorder run-event sampling state (see run())
        self._run_events = 0
        self._run_dur_ewma: Optional[float] = None
        # jitted identity-copy for snapshot_device_state (stf.checkpoint
        # barrier snapshots); jax.jit's own cache handles new key sets /
        # avals, so one callable serves every snapshot shape
        self._snapshot_copy_fn = None
        live_sessions.add(self)

    def _classify_var(self, name: str) -> Optional[str]:
        """Ledger class for a store entry (stf.telemetry.memory):
        kv_cache hints land in ``store.classes`` at trace time; slot
        variables carry ``_mem_class`` (train/slot_creator and the
        fused flat layout both mark theirs); trainable Variables are
        weights; everything else (global_step, counters, EMA shadows)
        is generic device state."""
        from ..telemetry import memory as _memory_mod

        registry = self._graph._scoped_state.get(
            "__vars_by_store_name__", {})
        var = registry.get(name)
        if var is None:
            return _memory_mod.CLASS_STATE
        cls = getattr(var, "_mem_class", None)
        if cls:
            return cls
        return _memory_mod.CLASS_WEIGHTS if var.trainable \
            else _memory_mod.CLASS_STATE

    # -- stf.analysis hooks --------------------------------------------------
    def _hazard_mode(self) -> str:
        from .. import analysis

        mode = getattr(self._config, "variable_hazard_mode", None) \
            if self._config is not None else None
        return mode or analysis.get_hazard_mode()

    def _numerics_mode(self) -> str:
        """Resolved numerics-health mode for this Session's plans:
        ConfigProto(numerics=...) > the stf.debug.numerics process
        default / STF_NUMERICS > "off". The process default is read
        without forcing the debug.numerics import: when the module is
        not loaded, the env var alone decides (the module reads the
        same var on first import, so the answers agree)."""
        mode = getattr(self._config, "numerics", None) \
            if self._config is not None else None
        if mode is not None:
            return mode
        mod = sys.modules.get("simple_tensorflow_tpu.debug.numerics")
        if mod is not None:
            return mod.get_numerics_mode()
        env = os.environ.get("STF_NUMERICS", "").strip().lower()
        return env if env in ("metrics", "raise", "dump") else "off"

    def _verify_graph_now(self, construction: bool) -> None:
        """graph_analysis="warn"|"strict": verify the session's graph
        (full level — structural + abstract-eval re-checks) and either
        log or raise on ERROR diagnostics."""
        from .. import analysis
        from ..platform import tf_logging as logging

        diags = analysis.verify_graph(self._graph, level="full")
        errs = analysis.errors(diags)
        for d in diags:
            if not d.is_error:
                logging.warning("graph analysis: %s", d.format())
        if errs:
            msg = analysis.format_report(
                errs, header="graph verification failed at session "
                             "construction:")
            if self._analysis_mode == "strict":
                raise errors.InvalidArgumentError(None, None, msg)
            logging.warning("%s", msg)

    @staticmethod
    def _resolve_target(target):
        """Route the TF-1 ``Session(target)`` parameter (ref:
        core/distributed_runtime/rpc/grpc_session.cc — the reference
        attaches to a grpc master; rounds ≤4 silently ignored it).

        TPU-native mapping: multi-host execution is SPMD over the global
        mesh after ``stf.train.Server`` runs ``jax.distributed`` bootstrap
        — every process runs the same Session against all hosts' devices,
        so "attach" means "verify the bootstrap happened / perform it",
        never "proxy graphs to a remote master".

        - ``""``           → process-local session (single host).
        - ``"stf://..."``  → a Server's target: require its bootstrap.
        - ``"grpc://h:p"`` → attach to that coordinator: accept if the
          running Server used it; else bootstrap from STF_NUM_PROCESSES /
          STF_PROCESS_ID env; else FailedPrecondition with guidance.
        - anything else    → UnimplementedError (silent ignore is the one
          forbidden outcome).
        """
        if not target:
            return ""
        if not isinstance(target, (str, bytes)):
            raise TypeError(f"target must be a string, got {target!r}")
        if isinstance(target, bytes):
            target = target.decode()
        from ..framework import errors as errors_mod
        from ..train import server_lib

        if target.startswith("stf://"):
            if not server_lib.Server._started:
                raise errors_mod.FailedPreconditionError(
                    None, None,
                    f"Session target {target!r} names a stf.train.Server, "
                    "but no Server has started in this process. Construct "
                    "stf.train.Server(cluster_spec, job_name=..., "
                    "task_index=...) first — it runs the jax.distributed "
                    "bootstrap that gives this session the global device "
                    "mesh.")
            return target
        if target.startswith("grpc://"):
            addr = target[len("grpc://"):]
            if server_lib.Server._started:
                coord = server_lib.Server._coordinator
                if coord is not None and addr not in (coord, ""):
                    raise errors_mod.InvalidArgumentError(
                        None, None,
                        f"Session target grpc://{addr} does not match the "
                        f"running Server's coordinator {coord!r}; one "
                        "process attaches to exactly one cluster.")
                return target
            num = os.environ.get("STF_NUM_PROCESSES")
            pid = os.environ.get("STF_PROCESS_ID")
            if num and pid:
                import jax

                jax.distributed.initialize(coordinator_address=addr,
                                           num_processes=int(num),
                                           process_id=int(pid))
                server_lib.Server._started = True
                server_lib.Server._coordinator = addr
                return target
            raise errors_mod.FailedPreconditionError(
                None, None,
                f"Session target grpc://{addr}: no jax.distributed "
                "bootstrap is active. Either construct stf.train.Server "
                "with the ClusterSpec (preferred), or set "
                "STF_NUM_PROCESSES and STF_PROCESS_ID so the session can "
                "attach to the coordinator itself.")
        raise errors_mod.UnimplementedError(
            None, None,
            f"Session target {target!r} is not supported: use \"\" "
            "(local), a Server.target, or \"grpc://host:port\" of the "
            "cluster coordinator.")

    # -- session handles -----------------------------------------------------
    def _register_handle(self, value, dtype):
        with self._lock:
            self._handle_counter += 1
            key = f"stf_handle_{self._handle_counter}:{dtype.name}"
            self._handles[key] = value
        return key

    def _handle_value(self, key):
        try:
            return self._handles[key]
        except KeyError:
            raise errors.InvalidArgumentError(
                None, None,
                f"Unknown session handle {key!r} (deleted, or from a "
                "different Session)")

    def _delete_handle(self, key):
        self._handles.pop(key, None)

    # -- properties ----------------------------------------------------------
    @property
    def graph(self):
        return self._graph

    @property
    def graph_def(self):
        return self._graph.as_graph_def()

    @property
    def sess_str(self):
        return ""

    def list_devices(self):
        from . import device_lib

        return device_lib.list_local_devices()

    def variable_value(self, var_or_name):
        """The DEVICE array backing a variable (jax.Array, sharding
        intact) — unlike ``run(var)``, which fetches a host copy. TPU-
        native introspection point for placement/sharding checks."""
        name = var_or_name if isinstance(var_or_name, str) else \
            getattr(var_or_name, "_var_name", None) or var_or_name.op.name
        store = self._variable_store.values
        if name not in store:
            # A read tensor / ref was passed: its op name carries scope
            # suffixes ("/read", ":0") the store is not keyed by. Resolve
            # through the graph's variable registry before giving up.
            registry = self._graph._scoped_state.get(
                "__vars_by_store_name__", {})
            stripped = name.split(":")[0]
            if stripped.endswith("/read"):
                stripped = stripped[:-len("/read")]
            if stripped in store:
                return store[stripped]
            var = registry.get(stripped)
            if var is not None and var._var_name in store:
                return store[var._var_name]
            raise KeyError(
                f"No variable state named {name!r} (argument must be a "
                f"Variable, its read tensor, or a store name); initialized "
                f"variables: {sorted(store)[:10]}...")
        return store[name]

    # -- barrier snapshots (stf.checkpoint; docs/CHECKPOINT.md) --------------
    def snapshot_device_state(self, names=None):
        """Donation-safe point-in-time snapshot of device-resident
        variable state, for async checkpointing.

        Returns ``({store_name: device_copy}, host_state)``. The copies
        are made ON DEVICE under the session's device lock — so the
        snapshot can never interleave with a step, and the live store
        arrays (which the next step's executable will DONATE and
        thereby invalidate) are never handed out. The copy dispatch is
        asynchronous; the caller (normally the ``stf_ckpt_writer``
        thread) pays the D2H transfer at ``np.asarray`` time, off the
        step loop. Until then the snapshot pins one extra copy of the
        named state in device memory.

        ``host_state`` is the non-device half a resume needs, captured
        at the same barrier: the RNG run counter and every data
        iterator's position (see ``snapshot_host_state``).
        """
        import jax

        with self._lock:
            store = self._variable_store
            wanted = sorted(store.values) if names is None else list(names)
            missing = [n for n in wanted if n not in store.values]
            if missing:
                raise errors.FailedPreconditionError(
                    None, None,
                    f"snapshot_device_state: variable(s) "
                    f"{sorted(missing)} uninitialized")
            if self._snapshot_copy_fn is None:
                import jax.numpy as jnp

                self._snapshot_copy_fn = jax.jit(
                    lambda d: {k: jnp.copy(v) for k, v in d.items()})
            copies = self._snapshot_copy_fn(
                {n: store.values[n] for n in wanted})
            host_state = self.snapshot_host_state()
        return copies, host_state

    def snapshot_host_state(self):
        """Session RNG position + data-iterator positions — the host
        half of a training-state checkpoint (SURVEY §5: resume restores
        global_step, optimizer slots, RNG key, data-pipeline epoch).
        The session RNG is (graph seed, run counter), so saving the
        counter is saving the key-stream position."""
        state = {"rng_run_counter": self._run_counter}
        try:
            from ..data import dataset as dataset_mod

            its = dataset_mod.iterator_registry(self._graph)
            if its:
                state["iterators"] = {name: it.save_state()
                                      for name, it in its.items()}
        except Exception:  # noqa: BLE001 — data module optional here
            pass
        return state

    # -- lifecycle -----------------------------------------------------------
    def close(self):
        self._closed = True
        # release this session's HBM-ledger accounting: store entries
        # (weights/slots/caches) and every registered AOT executable
        from ..telemetry import memory as _memory_mod

        ledger = _memory_mod.get_ledger()
        for step in list(self._cache.values()):
            ledger.release(step.compiled_mem_token)
            step.compiled_mem_token = None
            for exe in step.aot_cache.values():
                ledger.release(getattr(exe, "mem_token", None))
        self._cache.clear()
        self._variable_store.release_ledger()

    def __enter__(self):
        if not hasattr(_default_session_stack, "stack"):
            _default_session_stack.stack = []
        _default_session_stack.stack.append(self)
        return self

    def __exit__(self, *exc):
        _default_session_stack.stack.pop()
        self.close()
        return False

    def as_default(self):
        import contextlib

        @contextlib.contextmanager
        def ctx():
            if not hasattr(_default_session_stack, "stack"):
                _default_session_stack.stack = []
            _default_session_stack.stack.append(self)
            try:
                yield self
            finally:
                _default_session_stack.stack.pop()

        return ctx()

    # -- run -----------------------------------------------------------------
    def run(self, fetches, feed_dict=None, options=None, run_metadata=None):
        """(ref: python/client/session.py:767 ``BaseSession.run``)."""
        if self._closed:
            raise RuntimeError("Attempted to use a closed Session.")
        t0 = time.perf_counter()
        _metric_runs.get_cell().increase_by(1)
        trace = (options is not None and
                 getattr(options, "trace_level", 0) > 0 and
                 run_metadata is not None)
        timeout_ms = (int(getattr(options, "timeout_in_ms", 0) or 0)
                      if options is not None else 0)
        deadline = t0 + timeout_ms / 1000.0 if timeout_ms > 0 else None
        collector: Optional[Dict[str, Any]] = (
            {"start_s": t0} if trace else None)
        buf = monitoring.TraceBuffer() if trace else None
        import contextlib

        try:
            # the span opens outside the collection: a traced run's
            # timeline holds the phases, not one row over all of them
            with monitoring.traceme("session/run"), \
                    (monitoring.trace_collection(buf) if trace
                     else contextlib.nullcontext()):
                mapper = _FetchMapper(self._graph, fetches)
                feeds = self._normalize_feeds(feed_dict)
                values = self._run_elements(mapper.elements, feeds,
                                            collector=collector,
                                            deadline=deadline)
                with monitoring.traceme("session/assemble"):
                    out = mapper.rebuild(values)
        except Exception as e:
            # flight recorder (docs/OBSERVABILITY.md): the event is the
            # forensics breadcrumb; device-stage failures additionally
            # auto-dump from _execute_plan's on_error hook
            _flight_mod.get_recorder().record(
                "error", where="session_run",
                error_type=type(e).__name__, message=str(e)[:500])
            raise
        wall = time.perf_counter() - t0
        _metric_run_seconds.get_cell().add(wall)
        rec = _flight_mod.get_recorder()
        if rec.enabled:
            # run events are SAMPLED (first 16 runs, every 16th after,
            # plus any run >4x its trailing average — anomalies always
            # land): a 2 kHz training loop must not churn the ring, but
            # the slow outlier a postmortem needs is never dropped
            self._run_events += 1
            ewma = self._run_dur_ewma
            slow = ewma is not None and wall > 4.0 * ewma \
                and wall > 0.005
            self._run_dur_ewma = wall if ewma is None \
                else 0.98 * ewma + 0.02 * wall
            if slow or self._run_events <= 16 \
                    or self._run_events % 16 == 0:
                rec.record("run", dur_s=round(wall, 6),
                           n_fetches=len(mapper.elements),
                           traced=trace, slow=slow,
                           n_runs=self._run_events)
        if run_metadata is not None:
            stats = {
                "start_us": 0,
                "wall_time_s": wall,
                "nodes": [],
            }
            if buf is not None:
                stats["nodes"] = _drain_spans_to_nodes(buf, t0)
                stats["thread_names"] = dict(_TRACK_NAMES)
            if collector is not None:
                for k in ("compile_time_s", "fetch_bytes", "n_device_ops",
                          "n_host_ops", "flop_estimate"):
                    if k in collector:
                        stats[k] = collector[k]
            if isinstance(run_metadata, RunMetadata):
                run_metadata.step_stats = stats
                if collector is not None and collector.get("xla_cost"):
                    run_metadata.cost_graph = dict(collector["xla_cost"])
                rep = collector.get("sharding_report") \
                    if collector is not None else None
                if rep is not None:
                    run_metadata.cost_graph.setdefault(
                        "predicted_collectives", {
                            "total_bytes": rep.total_collective_bytes(),
                            "bytes_by_kind": rep.bytes_by_kind(),
                            "per_op": rep.per_op_collectives(),
                        })
            else:
                try:
                    run_metadata["wall_time_s"] = wall
                    run_metadata["step_stats"] = stats
                except TypeError:
                    pass
        return out

    # -- explicit plan/execute (the serving entry point) ---------------------
    def plan(self, fetches, feeds=None) -> "ExecutionPlan":
        """Plan ``fetches`` against the declared ``feeds`` WITHOUT
        executing: returns an :class:`ExecutionPlan` whose ``execute``
        runs the staged program and whose ``compile`` AOT-compiles it
        per feed-shape bucket. The plan is the same object ``run``
        would build and lives in the same executable cache — a
        ``run(fetches, feed_dict)`` with the identical signature is a
        cache hit on it.

        ``feeds``: the tensors (or names) executions will feed. Unlike
        ``run``, no values are needed here — planning uses feed-set
        membership only.
        """
        if self._closed:
            raise RuntimeError("Attempted to use a closed Session.")
        mapper = _FetchMapper(self._graph, fetches)
        feed_ts = [self._graph.as_graph_element(f, allow_tensor=True,
                                                allow_operation=False)
                   for f in (feeds or [])]
        feed_map: Dict[Tensor, Any] = {t: None for t in feed_ts}
        step = self._get_or_plan(mapper.elements, feed_map,
                                 count_fast_path=False)
        return ExecutionPlan(self, mapper, feed_ts, step,
                             self._cache_key(mapper.elements, feed_map))

    # -- multi-step fused run (device-resident training loop) ----------------
    def run_steps(self, fetches, n=None, feed_dict=None, feed_iterator=None,
                  stacked_feeds=None, output_mode="last", options=None,
                  run_metadata=None):
        """Run ``fetches`` for ``n`` consecutive steps as ONE device
        program; see :meth:`_run_steps_body` for the full contract.

        ``options.trace_level >= SOFTWARE_TRACE`` with a RunMetadata
        traces the WINDOW (ISSUE 8): the fused path records its
        lifecycle spans (superbatch staging, plan phases, the blocking
        ``fused_device_execute``) into ``step_stats["nodes"]`` and
        breaks the fused window down by graph op on an attributed
        track — cost-model per-op estimates scaled into the measured
        window seconds — instead of one opaque bar
        (docs/OBSERVABILITY.md). ProfilerHook drives exactly this when
        a trigger lands on a fused window boundary."""
        trace = (options is not None
                 and getattr(options, "trace_level", 0) > 0
                 and isinstance(run_metadata, RunMetadata))
        if not trace:
            return self._run_steps_body(
                fetches, n, feed_dict, feed_iterator, stacked_feeds,
                output_mode, options, run_metadata)
        buf = monitoring.TraceBuffer()
        with monitoring.trace_collection(buf):
            return self._run_steps_body(
                fetches, n, feed_dict, feed_iterator, stacked_feeds,
                output_mode, options, run_metadata, trace_buf=buf)

    def _run_steps_body(self, fetches, n=None, feed_dict=None,
                        feed_iterator=None, stacked_feeds=None,
                        output_mode="last", options=None,
                        run_metadata=None, trace_buf=None):
        """Run ``fetches`` for ``n`` consecutive steps as ONE device
        program (the classic TPU in-loop training pattern, arXiv
        1605.08695 §4.4 / 1909.09756): the per-step plan is lowered into
        a ``jax.lax.scan`` over N device-staged batches, variables
        thread through the donated carry (updated in-place in HBM),
        per-step RNG keys split on-device, and host dispatch is paid
        once per window instead of once per step.

        Feeds — combinable:
          feed_dict:      fed identically on every step (hyperparams, or
                          a constant batch).
          feed_iterator:  iterable of per-step feed dicts; n are pulled
                          and stacked into a superbatch on the host.
          stacked_feeds:  {tensor: array} whose leading dim is n — a
                          prestacked superbatch (e.g. from
                          ``stf.data.Dataset.prefetch_to_device(
                          superbatch=n)``), staged without re-stacking.

        output_mode: "last" (default) returns each fetch's value from
        the final step; "stacked" returns every fetch with a leading
        per-step dim of n. Fetched Operations return None either way.

        Fusion requires a loop-safe plan (stf.analysis.certify_loop_safe):
        no host-stage ops (iterators, queues, py_func), no host sinks
        (summaries), no io-effectful device ops (Print), no
        CheckNumerics/Assert, and every assigned variable already
        initialized. An unsafe plan FALLS BACK to n sequential
        ``run`` calls — same results, none of the amortization — with a
        structured diagnostic naming the blocking op, counted per reason
        on ``/stf/session/loop_fusion_fallbacks``.

        Bit-compatible with n sequential ``run`` calls: same per-step
        RNG counters, same variable threading, same lowering rules.
        """
        if self._closed:
            raise RuntimeError("Attempted to use a closed Session.")
        if output_mode not in ("last", "stacked"):
            raise ValueError(
                f"output_mode must be 'last' or 'stacked', "
                f"got {output_mode!r}")
        if n is None:
            n = getattr(self._config, "loop_fusion_steps", 1) \
                if self._config is not None else 1
        n = int(n)
        if n < 1:
            raise ValueError(f"run_steps needs n >= 1, got {n}")
        t0 = time.perf_counter()
        # RunOptions.timeout_in_ms bounds the WINDOW's blocking wait
        # (same commit-then-detect contract as run: state commits before
        # the wait, so a timeout never corrupts the session)
        timeout_ms = (int(getattr(options, "timeout_in_ms", 0) or 0)
                      if options is not None else 0)
        deadline = t0 + timeout_ms / 1000.0 if timeout_ms > 0 else None
        mapper = _FetchMapper(self._graph, fetches)
        const_feeds = self._normalize_feeds(feed_dict)

        step_feeds: Optional[List[Dict[Tensor, Any]]] = None
        if feed_iterator is not None:
            it = iter(feed_iterator)
            step_feeds = []
            for i in range(n):
                try:
                    fd = next(it)
                except StopIteration:
                    raise errors.OutOfRangeError(
                        None, None,
                        f"run_steps: feed_iterator exhausted after {i} of "
                        f"{n} per-step feeds")
                step_feeds.append(self._normalize_feeds(fd))
            keys0 = set(step_feeds[0])
            for i, fd in enumerate(step_feeds[1:], 1):
                if set(fd) != keys0:
                    raise ValueError(
                        "run_steps: feed_iterator must feed the same "
                        f"tensors every step (step 0 fed "
                        f"{sorted(t.name for t in keys0)}, step {i} fed "
                        f"{sorted(t.name for t in fd)})")

        superbatch: Dict[Tensor, Any] = {}
        if stacked_feeds:
            import jax

            for k, v in stacked_feeds.items():
                t = self._graph.as_graph_element(k, allow_tensor=True,
                                                 allow_operation=False)
                if not isinstance(v, jax.Array):
                    v = np.asarray(v) if t.dtype.name == "string" else \
                        np.asarray(v, dtype=t.dtype.base_dtype.np_dtype)
                if v.ndim < 1 or v.shape[0] != n:
                    raise ValueError(
                        f"run_steps: stacked feed for {t.name} must have "
                        f"leading dim n={n}, got shape {tuple(v.shape)}")
                if not t.shape.is_compatible_with(v.shape[1:]):
                    raise ValueError(
                        f"run_steps: per-step slice shape {v.shape[1:]} "
                        f"incompatible with tensor {t.name} shape "
                        f"{t.shape}")
                superbatch[t] = v
        if step_feeds is not None:
            dup = set(step_feeds[0]) & set(superbatch)
            if dup:
                raise ValueError(
                    "run_steps: tensors fed both via stacked_feeds and "
                    f"feed_iterator: {sorted(t.name for t in dup)}")
            with monitoring.traceme("session/superbatch_stage", n_steps=n,
                                    n_feeds=len(step_feeds[0])):
                for t in step_feeds[0]:
                    rows = [fd[t] for fd in step_feeds]
                    superbatch[t] = (np.stack([np.asarray(r) for r in rows])
                                     if t.dtype.name != "string"
                                     else np.stack(rows))
        overlap = set(const_feeds) & set(superbatch)
        if overlap:
            raise ValueError(
                "run_steps: tensors fed both per-window (feed_dict) and "
                f"per-step: {sorted(t.name for t in overlap)}")
        _check_deadline(deadline, "superbatch staging")

        all_feeds: Dict[Tensor, Any] = dict(const_feeds)
        for t in superbatch:
            all_feeds[t] = None  # feed-set membership is what planning uses
        key = self._cache_key(mapper.elements, all_feeds)
        step = self._get_or_plan(mapper.elements, all_feeds,
                                 count_fast_path=False)

        from .. import analysis

        # certification is O(plan); cache the plan-static part and only
        # re-check the store-dependent part (uninitialized writes) per
        # call — the store's key set changes only at initialization
        cached = step.fusion_diags
        if cached is None:
            static_diags = analysis.loop_safety.certify_plan(
                step.device_ops if step.has_device_stage else [],
                step.host_plan, step.post_host_plan,
                variable_store=None)
            written = analysis.loop_safety._written_var_names(
                step.device_ops if step.has_device_stage else [])
            step.fusion_diags = cached = (static_diags, written)
        static_diags, written = cached
        diags = list(static_diags)
        missing = sorted(written - set(self._variable_store.values))
        if missing:
            diags.append(analysis.loop_safety.uninitialized_write_diag(
                missing))
        # pure host sinks defer to once-per-window only under "last":
        # "stacked" must serialize them per step, so it falls back
        if n > 1 and output_mode == "stacked" and any(
                getattr(op.op_def, "host_sink_pure", False)
                for op in step.post_host_plan):
            diags.append(analysis.loop_safety.stacked_host_sink_diag(
                step.post_host_plan))
        if diags or n == 1:
            if diags and n > 1:
                reasons = analysis.loop_safety.fallback_reasons(diags)
                for r in reasons:
                    _metric_fusion_fallback.get_cell(r).increase_by(1)
                _flight_mod.get_recorder().record(
                    "fused_window_fallback", n_steps=n,
                    reasons=sorted(reasons))
                warn_key = key[:2] + (tuple(reasons),)
                if warn_key not in self._fusion_warned:
                    self._fusion_warned.add(warn_key)
                    from ..platform import tf_logging as logging

                    logging.warning(
                        "run_steps: falling back to %d sequential runs:\n%s",
                        n, analysis.format_report(
                            diags, header="loop fusion refused:"))
            out = self._run_steps_unfused(mapper, n, const_feeds,
                                          superbatch, step_feeds,
                                          output_mode, options, run_metadata)
            if run_metadata is not None and isinstance(run_metadata,
                                                       RunMetadata):
                run_metadata.step_stats["loop_fusion"] = {
                    "fused": False, "n_steps": n,
                    "diagnostics": [d.to_dict() for d in diags],
                }
            return out

        # -- fused path ------------------------------------------------------
        missing = [t for t in step.feed_tensors
                   if t not in const_feeds and t not in superbatch]
        if missing:
            raise errors.InvalidArgumentError(
                None, None,
                "run_steps: the device program needs feeds for "
                f"{sorted(t.name for t in missing)}")
        xs_names = frozenset(t.name for t in step.feed_tensors
                             if t in superbatch)
        fused = step.fused.get((n, output_mode, xs_names))
        if fused is None:
            jitted, fused_msgs = self._build_fused(step, n, output_mode,
                                                   xs_names)
            fused = {"jitted": jitted, "check_msgs": fused_msgs,
                     "n_calls": 0}
            step.fused[(n, output_mode, xs_names)] = fused
        const_args = {t.name: self._staged_feed(step, t, const_feeds[t])
                      for t in step.feed_tensors if t in const_feeds}
        xs_args = {t.name: superbatch[t] for t in step.feed_tensors
                   if t in superbatch}
        from ..telemetry import watchdog as _watchdog_mod

        wd = _watchdog_mod.get_watchdog()
        wd_token = None
        try:
            with self._lock:
                self._ensure_base_key()
                c0 = self._run_counter + 1
                if step.uses_rng:
                    # RNG-free windows leave the counter alone (matching
                    # n sequential runs under the same gating)
                    self._run_counter += n
                ctrs = np.arange(c0, c0 + n, dtype=np.uint32)
                state = self._variable_store.values
                first_call = fused["n_calls"] == 0
                if not first_call:
                    # wedge watchdog (ISSUE 8): a warm window that blows
                    # 10x past its trailing average is hung, not slow —
                    # snapshot every thread's stack while it still hangs.
                    # First calls are exempt (they include the compile).
                    wd_deadline = _watchdog_mod.deadline_for(
                        fused.get("ewma"))
                    if wd_deadline:
                        wd_token = wd.arm("fused_window", wd_deadline,
                                          n_steps=n)
                d_t0 = time.perf_counter()
                with monitoring.traceme("session/fused_device_execute",
                                        n_steps=n):
                    try:
                        outs, check_flags, new_state = fused["jitted"](
                            dict(state), const_args, xs_args,
                            self._base_key, ctrs)
                        if trace_buf is not None:
                            # traced window: block inside the span so it
                            # covers device execution, not just dispatch
                            import jax

                            jax.block_until_ready(list(outs))
                    except Exception as e:
                        _flight_mod.get_recorder().on_error(
                            e, where="fused_device_execute", n_steps=n,
                            plan_memory=((step.xla_cost or {})
                                         .get("memory")
                                         or step.memory_estimate))
                        raise
                self._variable_store.values = dict(new_state)
                self._apply_declared_shardings(new_state.keys())
                self._variable_store.sync_ledger()
                fused["n_calls"] += 1
                _metric_fused_steps.get_cell().increase_by(n)
                if check_flags:
                    # CheckNumerics/Assert rode the scan ys: inspect
                    # AFTER the window committed (post-commit detection —
                    # the documented relaxation that lets checks fuse;
                    # recovery is checkpoint restore)
                    import jax

                    fl = np.stack([np.asarray(f) for f in
                                   jax.device_get(list(check_flags))])
                    if fl.any():
                        step_bad = fl.any(axis=0)
                        k = int(np.argmax(step_bad))
                        bad = [m for m, f in zip(fused["check_msgs"],
                                                 fl[:, k]) if f]
                        raise errors.InvalidArgumentError(
                            None, None,
                            "; ".join(bad) + f" (first failed at fused "
                            f"window step {k} of {n}; state committed "
                            "through the window — restore a checkpoint "
                            "to recover)")
                if deadline is not None:
                    # state committed above: a deadline abort is detection
                    # only and leaves the session consistent
                    _block_with_deadline(list(outs), deadline)
                if first_call:
                    # untraced compile convention: first-call seconds
                    # include the (dominant) XLA compile of the fused loop
                    _metric_compile_seconds.get_cell().add(
                        time.perf_counter() - d_t0)

            # numerics plane: observe every step of the window (the
            # health fetch kept its per-step axis), AFTER the commit
            # and outside the lock — forensics/raise per mode
            if (step.numerics is not None
                    and step.numerics["index"] is not None):
                self._observe_numerics_window(step, outs, const_args,
                                              xs_args, state, ctrs, n)

            dev_pos = {t: i for i, t in enumerate(step.device_fetches)}
            stacked = output_mode == "stacked"
            num_idx = step.numerics["index"] \
                if step.numerics is not None else None

            # Post-host stage, ONCE per window ("last" mode only —
            # "stacked" plans with host sinks fell back above): pure
            # host sinks (host_sink_pure summary ops) consume the
            # window's final-step device values, so a histogram in the
            # train graph no longer splits the fused window.
            host_env: Dict[Tensor, Any] = {}
            if step.post_host_plan:
                with monitoring.traceme(
                        "session/post_host_stage",
                        n_ops=len(step.post_host_plan)):
                    pctx = lowering_mod.LoweringContext(
                        self._variable_store.values, rng_root=None,
                        host=True, session=self)
                    pctx.alias = step.alias
                    pctx.func_plans = step.func_plans
                    pctx.env.update(step.const_env)
                    pctx.env.update(const_feeds)
                    for t, v in superbatch.items():
                        pctx.env[t] = v[-1]
                    for t in step.post_host_inputs:
                        v = outs[dev_pos[t]]
                        if dev_pos[t] == num_idx:
                            v = v[-1]
                        if t in step.raw_post_inputs:
                            pctx.env[t] = v
                        else:
                            pctx.env[t] = (np.asarray(v)
                                           if t.dtype.name != "string"
                                           else v)
                    lowering_mod.execute_ops(pctx, step.post_host_plan,
                                             fed=set(pctx.env))
                    host_env = pctx.env

            def _per_step_const(v):
                v = np.asarray(v)
                return np.stack([v] * n) if stacked else v

            values: List[Any] = []
            for e in mapper.elements:
                if isinstance(e, Operation):
                    values.append(None)
                    continue
                r = step.alias.get(e, e)
                if e in const_feeds:
                    values.append(_per_step_const(const_feeds[e]))
                elif e in superbatch:
                    v = superbatch[e]
                    values.append(np.asarray(v) if stacked
                                  else np.asarray(v[-1]))
                elif r in dev_pos and r not in host_env:
                    v = outs[dev_pos[r]]
                    if not stacked and dev_pos[r] == num_idx:
                        v = v[-1]  # health kept its per-step axis
                    values.append(v if e.dtype.name == "string"
                                  else np.asarray(v))
                elif r in host_env:
                    v = host_env[r]
                    values.append(v if e.dtype.name == "string"
                                  else np.asarray(v))
                elif r in step.const_env:
                    values.append(_per_step_const(step.const_env[r]))
                elif r.op.type == "Const":
                    values.append(_per_step_const(r.op.attrs["value"]))
                else:
                    raise errors.InternalError(
                        None, e.op, f"Fetch {e.name} produced no value")
        finally:
            wd.disarm(wd_token)
        wall = time.perf_counter() - t0
        if not first_call:
            # trailing average feeds the next window's wedge deadline
            # (first calls excluded: compile time is not a wedge)
            prev = fused.get("ewma")
            fused["ewma"] = wall if prev is None else \
                0.7 * prev + 0.3 * wall
        rec = _flight_mod.get_recorder()
        if rec.enabled:
            rec.record("fused_window", n_steps=n, dur_s=round(wall, 6),
                       sec_per_step=round(wall / n, 9),
                       first_call=first_call)
        if run_metadata is not None and isinstance(run_metadata,
                                                   RunMetadata):
            stats: Dict[str, Any] = {
                "wall_time_s": wall,
                "loop_fusion": {"fused": True, "n_steps": n,
                                "sec_per_step": wall / n,
                                "run_counter_range": [int(c0),
                                                      int(c0 + n - 1)]},
            }
            if trace_buf is not None:
                stats["start_us"] = 0
                # bytes-over-time counter track (ISSUE 13): ledger
                # samples that landed during the window (store commits,
                # snapshot captures/releases) render as a chrome
                # counter series next to the op tracks
                from ..telemetry import memory as _memory_mod

                led = _memory_mod.get_ledger()
                samples = [{"t_us": max(0.0, (ts - t0) * 1e6),
                            "bytes": b}
                           for ts, b in led.history(since_mono=t0)]
                samples.append({"t_us": wall * 1e6,
                                "bytes": led.total_bytes()})
                stats["memory_samples"] = samples
                nodes = _drain_spans_to_nodes(trace_buf, t0)
                fw = [nd for nd in nodes
                      if nd["name"] == "fused_device_execute"]
                if fw:
                    # tentpole (4): break the fused window down by op
                    nodes.extend(_attributed_device_nodes(step, fw[-1]))
                stats["nodes"] = nodes
                stats["thread_names"] = {
                    **_TRACK_NAMES,
                    _ATTRIBUTED_TRACK: "device ops (attributed)"}
            run_metadata.step_stats = stats
        return mapper.rebuild(values)

    def _run_steps_unfused(self, mapper, n, const_feeds, superbatch,
                           step_feeds, output_mode, options, run_metadata):
        """Fallback: n sequential Session.run calls over the same feeds
        (identical semantics, no dispatch amortization)."""
        per_step: List[List[Any]] = []
        vals: List[Any] = []
        for i in range(n):
            fd: Dict[Tensor, Any] = dict(const_feeds)
            if step_feeds is not None:
                fd.update(step_feeds[i])
            else:
                for t, v in superbatch.items():
                    fd[t] = v[i]
            vals = self.run(mapper.elements, feed_dict=fd, options=options,
                            run_metadata=run_metadata if i == n - 1
                            else None)
            if output_mode == "stacked":
                per_step.append(vals)
        if output_mode == "stacked":
            vals = [None if col[0] is None
                    else np.stack([np.asarray(v) for v in col])
                    for col in zip(*per_step)]
        return mapper.rebuild(vals)

    def _build_fused(self, step, n, output_mode, xs_names):
        """Compile the N-step device loop for one plan: a lax.scan whose
        carry is the variable-store dict (donated — updates are in-place
        in HBM) and whose xs are the per-step feed slices plus the
        per-step RNG counters. Per-step keys are derived inside the
        program (fold_in(root, counter)) exactly as the single-step path
        does, so a fused window is bit-compatible with n sequential
        runs.

        Returns ``(jitted, check_msgs)``: the executable yields
        ``(outs, check_flags, final_state)`` where ``check_flags`` is a
        tuple of per-step ``[n]`` booleans, one per CheckNumerics/Assert
        in the plan (index-aligned with ``check_msgs``, filled at trace
        time). Checks ride the scan ys — fusion is never broken for
        them; the caller inspects the flags AFTER the window's state
        commit (post-commit detection, like the numerics plane)."""
        import jax
        import jax.numpy as jnp

        device_ops = step.device_ops
        boundary = list(step.feed_tensors)
        device_fetches = step.device_fetches
        plan_alias = step.alias
        plan_consts = step.const_env
        plan_func_plans = step.func_plans
        check_msgs: List[str] = []  # filled at trace time, index-aligned
        num_info = step.numerics
        # the health tensor keeps its per-step leading axis even under
        # "last": the observer needs every step's stats to localize the
        # exact anomalous step inside the window
        keep_stacked = {num_info["index"]} \
            if num_info is not None and num_info["index"] is not None \
            else set()

        def fused_fn(state, const_args, xs_args, rng_root, ctrs):
            def body(carry, x):
                xs, ctr = x
                rng = jax.random.fold_in(rng_root, ctr)
                ctx = lowering_mod.LoweringContext(dict(carry),
                                                   rng_root=rng,
                                                   session=self)
                ctx.alias = plan_alias
                ctx.func_plans = plan_func_plans
                for t, v in plan_consts.items():
                    if t.dtype.name != "string":
                        ctx.env[t] = jnp.asarray(v)
                for t in boundary:
                    ctx.env[t] = (xs[t.name] if t.name in xs
                                  else const_args[t.name])
                lowering_mod.execute_ops(ctx, device_ops,
                                         fed=set(boundary))
                fetch_vals = tuple(ctx.env[t] for t in device_fetches)
                check_msgs.clear()  # jit may trace more than once
                check_msgs.extend(m for m, _ in ctx.numeric_checks)
                flags = tuple(f for _, f in ctx.numeric_checks)
                return ctx.state, (fetch_vals, flags)

            final_state, (stacked, flags) = jax.lax.scan(
                body, dict(state), (xs_args, ctrs), length=n)
            if output_mode == "last":
                outs = tuple(v if i in keep_stacked else v[-1]
                             for i, v in enumerate(stacked))
            else:
                outs = stacked
            return outs, flags, final_state

        # numerics "dump" replays the window eagerly from the retained
        # window-entry state (bisect_window_and_dump) — donation off;
        # every other mode keeps the in-place HBM carry
        donate = () if (num_info is not None
                        and num_info["mode"] == "dump") else (0,)
        return jax.jit(fused_fn, donate_argnums=donate), check_msgs

    def _normalize_feeds(self, feed_dict) -> Dict[Tensor, np.ndarray]:
        feeds: Dict[Tensor, np.ndarray] = {}
        if not feed_dict:
            return feeds
        import jax

        from ..ops.session_ops import TensorHandle

        from ..framework.sparse_tensor import SparseTensor

        for k, v in feed_dict.items():
            if isinstance(k, SparseTensor):
                # TF-1 contract: feed a SparseTensor with a
                # SparseTensorValue (or (indices, values, dense_shape))
                # by expanding into its component tensors. A
                # static-shape sparse_placeholder keeps dense_shape as a
                # Const; validate the fed shape against it instead of
                # feeding it.
                try:
                    vi, vv, vs = v  # SparseTensorValue iterates as 3
                except (TypeError, ValueError):
                    raise TypeError(
                        f"Cannot feed {type(v).__name__} for SparseTensor"
                        f" {k.indices.name}: expected a SparseTensorValue"
                        " or an (indices, values, dense_shape) triple")
                from ..framework import constant_op as _const

                vs = np.asarray(vs)
                if vs.ndim != 1:
                    raise ValueError(
                        f"SparseTensor dense_shape must be rank-1; fed "
                        f"value has shape {vs.shape}")
                comps = {k.indices: vi, k.values: vv}
                static = _const.constant_value(k.dense_shape)
                if static is not None:
                    if vs.tolist() != list(np.asarray(static)):
                        raise ValueError(
                            f"SparseTensor {k.indices.name} has static "
                            f"dense_shape {list(static)}; fed value has "
                            f"dense_shape {vs.tolist()}")
                else:
                    comps[k.dense_shape] = vs
                feeds.update(self._normalize_feeds(comps))
                continue
            t = self._graph.as_graph_element(k, allow_tensor=True,
                                             allow_operation=False)
            if t.dtype.base_dtype.name in ("int64", "uint64", "float64"):
                # the once-per-process narrowing notice lives HERE, at
                # the session boundary, not per-op
                dtypes_mod.warn_64bit_narrowing_once(f"feed {t.name!r}")
            if isinstance(v, TensorHandle):
                # feed-by-handle: the holder receives the handle string;
                # GetSessionTensor resolves it to the pinned device array
                feeds[t] = np.asarray(v.handle, dtype=object)
                continue
            if isinstance(v, jax.Array):
                # Device-resident feed: no host round-trip (input pipelines
                # stage batches into HBM via data.prefetch_to_device).
                arr = v if str(v.dtype) == t.dtype.base_dtype.np_dtype.name \
                    or v.dtype == t.dtype.base_dtype.np_dtype else \
                    v.astype(t.dtype.base_dtype.np_dtype)
            elif t.dtype.name == "string":
                arr = np.asarray(v, dtype=object)
            else:
                arr = np.asarray(v, dtype=t.dtype.base_dtype.np_dtype)
            if not t.shape.is_compatible_with(arr.shape):
                raise ValueError(
                    f"Cannot feed value of shape {arr.shape} for tensor "
                    f"{t.name} with shape {t.shape}")
            feeds[t] = arr
        return feeds

    def _cache_key(self, elements, feed_tensors):
        # graph growth never invalidates a compiled step (append-only
        # IR), but an in-place FuncGraph body rewrite
        # (optimizer.optimize_graph_functions) must: the rewrite version
        # is part of every key, so stale jitted steps are simply never
        # hit again
        return (tuple(e.name if isinstance(e, Tensor) else "(op)" + e.name
                      for e in elements),
                tuple(sorted(t.name for t in feed_tensors)),
                getattr(self._graph, "_rewrite_version", 0))

    def _miss_reason(self, key) -> str:
        """Why this (fetches, feeds) signature needs a fresh plan — the
        retrace-reason label on the executable-cache miss counter. Only
        two reasons exist: the cache key is (fetch-sig, feed-sig,
        rewrite_version), so a miss on a known signature can only mean
        the rewrite version moved (append-only graph growth never
        invalidates a plan)."""
        sig = key[:2]
        prev = self._sig_versions.get(sig)
        self._sig_versions[sig] = key[2]
        if prev is not None and prev != key[2]:
            return "rewrite_version_bump"
        return "new_fetch_feed_signature"

    def _get_or_plan(self, elements: List[Any],
                     feeds: Dict[Tensor, Any],
                     count_fast_path: bool = True) -> _CompiledStep:
        """PLAN layer: resolve the (fetches, feeds) signature to a
        compiled step — executable-cache lookup, else a full
        prune/optimize/analyze/lower plan. Shared by run, run_steps,
        and Session.plan (the serving entry point), so every path pays
        for planning exactly once per signature."""
        key = self._cache_key(elements, feeds)
        step = self._cache.get(key)
        if step is None:
            _metric_cache_misses.get_cell(
                self._miss_reason(key)).increase_by(1)
            step = self._plan(elements, feeds)
            # concurrent first calls may both compile; the first insert
            # wins and the others adopt it (n_calls stays coherent)
            step = self._cache.setdefault(key, step)
        else:
            _metric_cache_hits.get_cell().increase_by(1)
            if (count_fast_path and step.has_device_stage
                    and not step.host_plan and not step.post_host_plan):
                # steady-state fast path: a warm pure-device program —
                # no re-plan, no analysis/lint, staging slots committed
                _metric_fast_path.get_cell().increase_by(1)
        return step

    def _run_elements(self, elements: List[Any],
                      feeds: Dict[Tensor, np.ndarray], collector=None,
                      deadline=None):
        step = self._get_or_plan(elements, feeds)
        return self._execute_plan(step, elements, feeds,
                                  collector=collector, deadline=deadline)

    def _execute_plan(self, step: _CompiledStep, elements: List[Any],
                      feeds: Dict[Tensor, np.ndarray], collector=None,
                      deadline=None, async_fetches=None):
        """EXECUTE layer: stage feeds, dispatch the device program, run
        host stages, assemble fetch values for an already-planned step.
        ``async_fetches`` overrides ConfigProto(async_fetches) per call
        (ModelServer executes with futures regardless of config)."""
        # Host stage -------------------------------------------------------
        host_env: Dict[Tensor, Any] = {}
        if step.host_plan:
            with monitoring.traceme("session/host_stage",
                                    n_ops=len(step.host_plan)):
                hctx = lowering_mod.LoweringContext(
                    self._variable_store.values, rng_root=None,
                    feeds=dict(feeds), host=True, session=self)
                hctx.alias = step.alias
                hctx.func_plans = step.func_plans
                hctx.env.update(step.const_env)
                hctx.env.update(feeds)
                lowering_mod.execute_ops(hctx, step.host_plan,
                                         fed=set(feeds))
                host_env = hctx.env
            if collector is not None:
                collector["n_host_ops"] = len(step.host_plan)
            _check_deadline(deadline, "the host stage")

        # Device stage -----------------------------------------------------
        device_results: List[Any] = []
        new_state = None
        if step.has_device_stage:
            # TF-1 sessions are thread-safe: concurrent run() calls
            # serialize their DEVICE stage (execute + state commit) —
            # unsynchronized, two steps would read the same donated
            # state (deleted-buffer errors) and the later commit
            # would silently drop the earlier update. Host stages
            # stay concurrent: a blocked queue dequeue must not
            # deadlock the producer thread that would fill it.
            with self._lock:
                # the executor's own code ahead of the feeds: rng,
                # transfer guards (the wait for the lock is session/run's)
                with monitoring.traceme("session/prepare"):
                    rng_key, rng_ctr = self._rng_args(consume=step.uses_rng)
                    guard_on = (self._config is not None and
                                getattr(self._config, "transfer_guard",
                                        "allow") != "allow"
                                and step.n_calls >= 2)
                    if guard_on:
                        # guards run BEFORE execution so a "disallow" raise
                        # can never land after the variable updates commit.
                        # Feeds: a big host-numpy feed is an H2D transfer
                        # EVERY step. Fetches: sizes precomputed from static
                        # shapes at plan time (dynamic-shaped fetches are
                        # unguarded by design).
                        for t in step.feed_tensors:
                            val = feeds[t] if t in feeds else host_env[t]
                            if isinstance(val, np.ndarray):
                                self._transfer_guard(t.name, val.nbytes,
                                                     "feed")
                        for name, nbytes in step.fetch_nbytes:
                            self._transfer_guard(name, nbytes, "fetch")
                feed_args = {}
                with monitoring.traceme("session/stage_feeds",
                                        n_feeds=len(step.feed_tensors)):
                    for t in step.feed_tensors:
                        val = feeds[t] if t in feeds else host_env[t]
                        feed_args[t.name] = self._staged_feed(step, t, val)
                state = self._variable_store.values
                first_call = step.n_calls == 0
                if collector is not None:
                    self._prepare_executable_analysis(
                        step, state, feed_args, rng_key, rng_ctr,
                        first_call, collector)
                d_t0 = time.perf_counter()
                with monitoring.traceme("session/device_execute"):
                    try:
                        fetch_vals, new_state, check_flags = \
                            _call_step_executable(step, state, feed_args,
                                                  rng_key, rng_ctr)
                    except Exception as e:
                        # a device-program failure is the flight
                        # recorder's prime customer: record + auto-dump
                        # (rate-limited) so the ring around the crash
                        # survives the process. RESOURCE_EXHAUSTED
                        # additionally lands an `oom` event with the
                        # HBM-ledger snapshot + this plan's memory
                        # analysis (telemetry.memory OOM forensics).
                        _flight_mod.get_recorder().on_error(
                            e, where="device_execute",
                            n_device_ops=len(step.device_ops),
                            plan_memory=((step.xla_cost or {})
                                         .get("memory")
                                         or step.memory_estimate))
                        raise
                    if check_flags:
                        # inspect BEFORE committing state: a failed check
                        # must not apply NaN-contaminated updates (ref
                        # semantics: ops downstream of a failed
                        # CheckNumerics never run)
                        import jax

                        flags_np = np.asarray(jax.device_get(check_flags))
                        if flags_np.any():
                            bad = [m for m, f
                                   in zip(step.check_msgs, flags_np) if f]
                            raise errors.InvalidArgumentError(
                                None, None, "; ".join(bad))
                    with monitoring.traceme("session/commit"):
                        self._variable_store.values = dict(new_state)
                        self._apply_declared_shardings(new_state.keys())
                        self._variable_store.sync_ledger()
                    device_results = list(fetch_vals)
                    step.n_calls += 1
                    if collector is not None or deadline is not None:
                        # block so the span covers device execution, not
                        # just async dispatch; state committed above, so
                        # a deadline abort leaves the session consistent
                        _block_with_deadline(device_results, deadline)
                d_dur = time.perf_counter() - d_t0
                if first_call and collector is None:
                    # untraced first call: compile+first-run seconds
                    # (compile dominates; the traced path records a pure
                    # compile sample instead)
                    _metric_compile_seconds.get_cell().add(d_dur)
                if collector is not None:
                    if first_call:
                        collector.setdefault("compile_time_s", d_dur)
                    collector["n_device_ops"] = len(step.device_ops)
                    collector["fetch_bytes"] = int(sum(
                        getattr(v, "nbytes", 0) for v in fetch_vals))
                    if step.xla_cost:
                        collector["xla_cost"] = step.xla_cost
                    rep = step.join_sharding()
                    if rep is not None:
                        collector["sharding_report"] = rep

        # the executor's own code between the enqueue and the fetch
        with monitoring.traceme("session/assemble"):
            # numerics plane: inspect the packed health tensor AFTER
            # the commit (outside the lock — forensics must not block
            # concurrent steps). State through this step is already
            # committed; "raise" tells the user to restore a
            # checkpoint, "dump" re-executes from the retained
            # pre-step state to localize the first bad op.
            if (step.has_device_stage and step.numerics is not None
                    and step.numerics["index"] is not None):
                self._observe_numerics(step, device_results, feed_args,
                                       state, rng_key, rng_ctr)
            dev_map = dict(zip(step.device_fetches, device_results))
            # the donated state and the staged feeds die here, under a
            # name, and not with the frame (a hundred arrays: ~0.1 ms)
            state = new_state = fetch_vals = feed_args = None
            # async_fetches: device-produced fetches leave as lazy
            # FetchFutures riding jax async dispatch; the host transfer
            # happens at materialization (docs/PERFORMANCE.md)
            if async_fetches is None:
                async_on = (self._config is not None
                            and getattr(self._config, "async_fetches", False))
            else:
                async_on = bool(async_fetches)

        # Post-host stage (host sinks: summaries etc.) ----------------------
        if step.post_host_plan:
            with monitoring.traceme("session/post_host_stage",
                                    n_ops=len(step.post_host_plan)):
                pctx = lowering_mod.LoweringContext(
                    self._variable_store.values, rng_root=None, host=True,
                    session=self)
                pctx.alias = step.alias
                pctx.func_plans = step.func_plans
                pctx.env.update(step.const_env)
                pctx.env.update(host_env)
                pctx.env.update(feeds)
                for t, v in dev_map.items():
                    if t in step.raw_post_inputs:
                        pctx.env[t] = v  # stays a jax.Array (session handles)
                    else:
                        pctx.env[t] = (np.asarray(v)
                                       if t.dtype.name != "string" else v)
                lowering_mod.execute_ops(pctx, step.post_host_plan,
                                         fed=set(pctx.env))
                host_env = pctx.env
            _check_deadline(deadline, "the post-host stage")

        # Fetch ------------------------------------------------------------
        out = []
        # device values to materialize: their places in ``out``
        pending: List[int] = []
        with monitoring.traceme("session/fetch"):
            for e in elements:
                if isinstance(e, Operation):
                    out.append(None)
                    continue
                r = step.alias.get(e, e)  # CSE'd fetch -> canonical value
                if e in feeds:
                    out.append(feeds[e])
                elif r in dev_map and r not in host_env:
                    v = dev_map[r]
                    if e.dtype.name == "string":
                        out.append(v)
                    elif async_on:
                        out.append(FetchFuture(v))
                    else:
                        pending.append(len(out))
                        out.append(v)
                elif r in host_env:
                    if r.op.type == "GetSessionHandle":
                        from ..ops.session_ops import TensorHandle, _handle_str

                        out.append(TensorHandle(
                            _handle_str(host_env[r]),
                            r.op.attrs["dtype"], self))
                    else:
                        v = host_env[r]
                        # a raw device array can land here when the tensor
                        # also fed a GetSessionHandle op — fetches always
                        # return numpy (string tensors pass through)
                        if (not isinstance(v, np.ndarray)
                                and e.dtype.name != "string"):
                            v = np.asarray(v)
                        out.append(v)
                elif r in step.const_env:  # folded at plan time
                    out.append(step.const_env[r])
                else:  # e.g. string Const fetched directly
                    if r.op.type == "Const":
                        out.append(r.op.attrs["value"])
                    else:
                        raise errors.InternalError(
                            None, e.op, f"Fetch {e.name} produced no value")
            if pending:
                # the host first blocks on the step's results HERE (unless
                # a post-host stage or a deadline already did)
                _await_device([out[i] for i in pending])
                with monitoring.traceme("session/copy_to_host"):
                    for i in pending:
                        out[i] = np.asarray(out[i])
        return out

    def _observe_numerics(self, step, device_results, feed_args, state,
                          rng_key, rng_ctr):
        """Post-commit numerics-health observer for a plain (unfused)
        step: pull the packed [T, 4] health tensor off the fetch
        channel, feed the process HealthPlane (/stf/train/* metrics,
        /trainz), and on an anomaly run the mode's escalation — flight
        recorder event, first-bad-op bisector + dump ("dump"),
        structured raise ("raise"/"dump")."""
        import jax

        from ..debug import numerics as numerics_mod

        info = step.numerics
        health = np.asarray(
            jax.device_get(device_results[info["index"]]))
        plane = numerics_mod.get_plane()
        anomaly = plane.record_step(info["taps"], health,
                                    step=int(rng_ctr))
        if anomaly is None:
            return
        bad_op = dump_root = None
        if info["mode"] == "dump":
            try:
                bad_op, dump_root = numerics_mod.bisect_and_dump(
                    self, step, feed_args, state, rng_key, int(rng_ctr),
                    anomaly)
                plane.note_forensics(
                    first_bad_op=bad_op.name if bad_op else None,
                    dump_root=dump_root)
            except Exception as e:  # noqa: BLE001 — forensics advisory
                from ..platform import tf_logging as logging

                logging.warning(
                    "numerics: first-bad-op bisector failed: %s: %s",
                    type(e).__name__, e)
        self._record_numeric_event(anomaly, bad_op, dump_root)
        if info["mode"] in ("raise", "dump"):
            numerics_mod.raise_anomaly(anomaly, bad_op=bad_op,
                                       dump_root=dump_root)

    def _observe_numerics_window(self, step, outs, const_args, xs_args,
                                 pre_state, ctrs, n):
        """Post-commit observer for a fused N-step window: the health
        fetch keeps its per-step leading axis ([n, T, 4]) even under
        "last" output mode, so EVERY step in the window is recorded
        (the history ring and anomaly step index stay exact). The
        FIRST anomalous step drives forensics/raise."""
        import jax

        from ..debug import numerics as numerics_mod

        info = step.numerics
        health = np.asarray(jax.device_get(outs[info["index"]]))
        plane = numerics_mod.get_plane()
        first_anomaly = None
        bad_index = None
        for i in range(int(n)):
            anomaly = plane.record_step(info["taps"], health[i],
                                        step=int(ctrs[i]),
                                        window_index=i)
            if anomaly is not None and first_anomaly is None:
                first_anomaly = anomaly
                bad_index = i
        if first_anomaly is None:
            return
        bad_op = dump_root = None
        if info["mode"] == "dump":
            try:
                bad_op, dump_root = numerics_mod.bisect_window_and_dump(
                    self, step, const_args, xs_args, pre_state,
                    self._base_key, ctrs, bad_index, first_anomaly)
                plane.note_forensics(
                    first_bad_op=bad_op.name if bad_op else None,
                    dump_root=dump_root)
            except Exception as e:  # noqa: BLE001 — forensics advisory
                from ..platform import tf_logging as logging

                logging.warning(
                    "numerics: fused-window bisector failed: %s: %s",
                    type(e).__name__, e)
        self._record_numeric_event(first_anomaly, bad_op, dump_root)
        if info["mode"] in ("raise", "dump"):
            numerics_mod.raise_anomaly(first_anomaly, bad_op=bad_op,
                                       dump_root=dump_root)

    @staticmethod
    def _record_numeric_event(anomaly, bad_op, dump_root):
        rec = _flight_mod.get_recorder()
        if not rec.enabled:
            return
        rec.record(
            "numeric", step=anomaly["step"],
            window_index=anomaly.get("window_index"),
            n_bad_taps=len(anomaly["taps"]),
            taps=[{"name": b["name"], "kind": b["kind"],
                   "nonfinite_count": b["nonfinite_count"],
                   "max_abs": b["max_abs"]}
                  for b in anomaly["taps"][:8]],
            first_bad_op=bad_op.name if bad_op is not None else None,
            dump_root=dump_root)

    def _transfer_guard(self, name: str, nbytes: int, direction: str):
        """L0 transfer guard (SURVEY §1 L0): per-step host↔device
        transfers above the configured threshold are the classic silent
        TPU bottleneck. Modes (ConfigProto.transfer_guard): "allow" (off),
        "log" (warn once per tensor), "disallow" (raise with guidance)."""
        cfg = self._config
        mode = getattr(cfg, "transfer_guard", "allow") if cfg else "allow"
        if mode == "allow":
            return
        threshold = getattr(cfg, "transfer_guard_threshold_bytes", 1 << 20)
        if nbytes < threshold:
            return
        if direction == "feed":
            hint = ("stage batches on device via "
                    "stf.data.Dataset.prefetch_to_device (or feed "
                    "jax.Arrays) instead of per-step host numpy")
        else:
            hint = ("keep large results on device: fetch reduced "
                    "values, or consume the tensor in a later step")
        msg = (f"transfer guard: {direction} {name!r} moves {nbytes} "
               f"bytes host<->device EVERY step; {hint}")
        if mode == "disallow":
            raise errors.InvalidArgumentError(None, None, msg)
        if name not in self._guard_warned:
            self._guard_warned.add(name)
            from ..platform import tf_logging as logging

            logging.warning(msg)

    def _feed_sharding(self, step, tensor):
        """The NamedSharding a shard_feed-annotated placeholder stages
        with under the current mesh, else None. Two-level slot: whether
        a tensor is annotated at all is cached per (plan, tensor) — the
        common unannotated feed pays one dict hit — and the committed
        NamedSharding is cached per mesh identity, so the current mesh
        scope is honored every run (a plan may be warmed outside the
        ``with mesh:`` scope) while PartitionSpec/NamedSharding
        construction still leaves the steady-state loop."""
        try:
            spec = step.feed_shardings[tensor.name]
        except KeyError:
            spec = tensor.op.attrs.get("sharding")
            step.feed_shardings[tensor.name] = spec
        if spec is None:
            return None
        from ..parallel.mesh import current_mesh

        mesh = current_mesh()
        if mesh is None:
            return None
        import jax

        cached = step.feed_shardings.get((tensor.name, "ns"))
        if cached is None or cached[0] is not mesh:
            cached = (mesh, jax.sharding.NamedSharding(
                mesh.jax_mesh, jax.sharding.PartitionSpec(*spec)))
            step.feed_shardings[(tensor.name, "ns")] = cached
        return cached[1]

    def _staged_feed(self, step, tensor, value):
        """Hot-path feed staging (shard_feed-annotated placeholders get
        their NamedSharding so GSPMD partitions the step; each host
        contributes its slice on pods)."""
        ns = self._feed_sharding(step, tensor)
        if ns is None:
            return value
        import jax

        return jax.device_put(value, ns)

    def _apply_declared_shardings(self, names):
        """Move variables with a declared sharding onto the mesh (one-time
        per variable, right after first write — typically initialization)."""
        from ..parallel.mesh import current_mesh

        mesh = current_mesh()
        if mesh is None:
            return
        registry = self._graph._scoped_state.get("__vars_by_store_name__", {})
        store = self._variable_store
        for name in names:
            if name in store.shardings:
                continue
            var = registry.get(name)
            if var is None or var.sharding is None:
                continue
            import jax

            ns = jax.sharding.NamedSharding(
                mesh.jax_mesh, jax.sharding.PartitionSpec(*var.sharding))
            store.shardings[name] = ns
            store.values[name] = jax.device_put(store.values[name], ns)

    def _prepare_executable_analysis(self, step, state, feed_args, rng_key,
                                     rng_ctr, first_call, collector):
        """Traced runs only. First call: split jit-compile from execution
        via the AOT path (``lower().compile()``), keep the executable for
        later same-shape calls, and harvest XLA cost_analysis +
        memory_analysis into ``step.xla_cost``. Cache-hit runs whose
        executable was compiled untraced backfill cost_analysis from a
        re-lowering (no backend compile). Either way the extra work is
        paid once per executable and only under SOFTWARE_TRACE."""
        if step.compiled is not None or step.xla_cost is not None:
            return
        try:
            if first_call:
                c_t0 = time.perf_counter()
                with monitoring.traceme("session/jit_compile",
                                        n_ops=len(step.device_ops)):
                    lowered = step.jitted.lower(dict(state), feed_args,
                                                rng_key, rng_ctr)
                    step.compiled = lowered.compile()
                compile_s = time.perf_counter() - c_t0
                _metric_compile_seconds.get_cell().add(compile_s)
                collector["compile_time_s"] = compile_s
                step.xla_cost = _executable_analysis(lowered, step.compiled)
                if step.compiled_mem_token is None:
                    # AOT executable buffers account in the HBM ledger,
                    # sized from the harvested memory_analysis
                    from ..telemetry import memory as _memory_mod

                    code = int(((step.xla_cost or {}).get("memory")
                                or {}).get("generated_code_bytes", 0))
                    step.compiled_mem_token = \
                        _memory_mod.get_ledger().register(
                            "traced_executable", code,
                            _memory_mod.CLASS_EXECUTABLE,
                            self._variable_store.owner)
            else:
                with monitoring.traceme("session/cost_analysis"):
                    lowered = step.jitted.lower(dict(state), feed_args,
                                                rng_key, rng_ctr)
                    step.xla_cost = _executable_analysis(lowered, None)
        except Exception:
            step.compiled = None
            step.xla_cost = {}  # tried; executable exposes no analysis

    def _next_rng(self):
        import jax

        key, counter = self._rng_args()
        return jax.random.fold_in(key, counter)

    def _ensure_base_key(self):
        if self._base_key is None:
            import jax

            seed = self._graph.seed if self._graph.seed is not None else 0
            self._base_key = jax.random.key(seed)
        return self._base_key

    def _rng_args(self, consume: bool = True):
        """(base_key, step_counter) for the jitted path: the per-step
        fold_in happens INSIDE the compiled program (traced once, DCE'd
        by XLA when the step uses no RNG), so the host pays an eager
        fold_in — ~0.4 ms/step, 75% of all dispatch overhead when
        measured — on no step. Eager paths (partial_run, py_func) use
        _next_rng, which folds immediately.

        ``consume=False`` (plans whose ``uses_rng`` is False — no op
        declares an RNG effect) returns the next position WITHOUT
        advancing the counter: the value only feeds the executable's
        DCE'd fold_in argument, and not advancing means read-only runs
        never perturb the key stream a checkpoint resume replays
        (stf.checkpoint bit-exact-resume contract)."""
        self._ensure_base_key()
        if consume:
            self._run_counter += 1
            return self._base_key, np.uint32(self._run_counter)
        return self._base_key, np.uint32(self._run_counter + 1)

    # -- planning ------------------------------------------------------------
    def _plan_shard_factor_fn(self):
        """Per-tensor mesh shard factor for plan cost estimates
        (``fn(tensor) -> int``, framework/cost_model.estimate): committed
        store shardings and KV-cache ``_cache_sharding`` declarations
        divide RESIDENT/LIVE bytes so budget admission charges
        PER-DEVICE HBM — the same unit the ledger holds
        (``_device_nbytes``). A head-sharded tp=8 decode cache therefore
        requests 1/8 of its replicated footprint at plan time; a budget
        that refuses the replicated layout can still admit the sharded
        one. Returns None when nothing is sharded (common single-device
        case: cost_model skips the per-tensor hook entirely)."""
        from ..ops import kv_cache_ops as _kvc
        from ..parallel.mesh import current_mesh

        mesh = current_mesh()
        shardings = self._variable_store.shardings
        if mesh is None and not shardings:
            return None

        def _factor(t):
            op = t.op
            decl = op.attrs.get(_kvc.SHARDING_ATTR)
            if decl and mesh is not None:
                try:
                    _, axis = _kvc.parse_cache_sharding(decl)
                except ValueError:
                    axis = None
                if axis is not None and axis in mesh.shape:
                    return mesh.axis_size(axis)
            vn = op.attrs.get("var_name", op.name)
            # a list names several store entries (a fused update, a
            # paged attention's K and V pools): the op's output is none
            ns = shardings.get(vn) if isinstance(vn, str) else None
            if ns is not None:
                try:
                    shape = tuple(int(d) for d in t.shape)
                    full = part = 1
                    for d in shape:
                        full *= d
                    for d in ns.shard_shape(shape):
                        part *= int(d)
                    if part:
                        return max(1, full // part)
                except Exception:  # noqa: BLE001 — accounting only
                    return 1
            return 1

        return _factor

    def _estimate_plan_memory(self, elements, feeds) -> Dict[str, Any]:
        """Static cost-model peak/resident prediction for a plan
        (framework/cost_model liveness sweep) in the shape
        ``ExecutionPlan.memory_info`` and the budget admission share.
        Peak/resident are PER-DEVICE when shardings are committed
        (``_plan_shard_factor_fn``). Best-effort: an un-costable plan
        predicts zeros rather than failing the plan."""
        from ..framework import cost_model

        try:
            est = cost_model.estimate(
                list(elements), feeds=list(feeds),
                shard_factor_fn=self._plan_shard_factor_fn())
            peak = int(est.peak_bytes)
            resident = int(est.resident_bytes)
        except Exception:  # noqa: BLE001 — accounting only
            peak = resident = 0
        return {"predicted_peak_bytes": peak,
                "predicted_resident_bytes": resident,
                "predicted_transient_bytes": max(0, peak - resident)}

    def _admit_plan_memory(self, step, elements, feeds) -> None:
        """Budget admission at PLAN time (ISSUE 13): predicted peak
        minus the plan's already-ledgered resident state is the NEW
        device memory this plan asks for; over budget raises
        ResourceExhaustedError (with the ledger forensics) before the
        program ever compiles or launches."""
        step.memory_estimate = self._estimate_plan_memory(elements,
                                                          feeds)
        if not self._memory_budget or not step.has_device_stage:
            return
        # variables already resident in THIS session's store are in the
        # ledger — don't charge them twice
        store = self._variable_store.values
        seen: Set[str] = set()
        already = 0
        for op in step.device_ops:
            if op.type in ("VariableV2", "ReadVariable"):
                vn = op.attrs.get("var_name", op.name)
                if vn in seen:
                    continue
                seen.add(vn)
                arr = store.get(vn)
                if arr is not None:
                    # per-device, matching the ledger and the sharded
                    # cost estimate (_plan_shard_factor_fn)
                    already += _device_nbytes(arr)
        requested = max(
            0, step.memory_estimate["predicted_peak_bytes"] - already)
        from ..telemetry import memory as _memory_mod

        _memory_mod.check_budget(
            self._memory_budget, requested, "plan",
            owner=self._variable_store.owner,
            detail="cost-model predicted peak "
                   f"{step.memory_estimate['predicted_peak_bytes']} B "
                   f"(resident {already} B already ledgered)")

    def _maybe_auto_shard(self, pruned, fed_set, fetches):
        """ConfigProto(auto_shard=True): search PartitionSpecs over the
        first fed plan and commit the winner before compile
        (stf.analysis.autoshard). Defensive: a search failure logs and
        degrades to the unsearched layout, never sinks a plan."""
        cfg = self._config
        if not getattr(cfg, "auto_shard", False) or not fed_set:
            return pruned
        scoped = self._graph._scoped_state
        if scoped.get("__autoshard_applied__"):
            return pruned
        try:
            from ..parallel import mesh as mesh_mod

            mesh = mesh_mod.current_mesh()
        except Exception:
            mesh = None
        if mesh is None or getattr(mesh, "size", 1) <= 1:
            return pruned
        from ..platform import tf_logging as logging

        try:
            from ..analysis import autoshard as autoshard_mod

            # full fetch list (ops AND tensors: cost_model.estimate
            # takes both) — the canonical sess.run(train_op) fetches an
            # Operation only, and tensor-only fetches would silently
            # skip the per-shard peak/budget feasibility check; feeds
            # sorted by name so the search trajectory (group order,
            # anneal rng mapping) is deterministic across processes
            result = autoshard_mod.search_sharding(
                graph=self._graph, ops=pruned, mesh=mesh,
                fetches=list(fetches),
                feeds=sorted(fed_set, key=lambda t: t.name),
                budget_bytes=cfg.device_memory_budget_bytes)
            result.apply(graph=self._graph)
            scoped["__autoshard_applied__"] = result
            # state committed before the search (init plans) was placed
            # without the searched shardings: re-place it NOW so this
            # plan's lowering/compile sees the chosen layout
            if self._variable_store.values:
                self._apply_declared_shardings(
                    list(self._variable_store.values.keys()))
            logging.info(
                "auto_shard: committed searched layout (%d candidates, "
                "%.3fs, predicted collective bytes %d vs replicated "
                "%d)", result.candidates_priced, result.search_seconds,
                int(result.predicted["collective_bytes"]),
                int(result.baseline["collective_bytes"]))
        except Exception as e:  # noqa: BLE001 — advisory, never fatal
            logging.warning("auto_shard: search failed (%s: %s); "
                            "continuing with declared shardings",
                            type(e).__name__, e)
            scoped["__autoshard_applied__"] = True
        return pruned

    def _splice_commit_constraints(self, pruned, alias, const_env):
        """Insert registered committing ShardingConstraint ops
        (autoshard cut points) into the plan immediately after their
        input's producer: the constraint's lowering rebinds the traced
        value, so every later consumer reads the committed layout. Ops
        whose input was folded away, or that are already in the plan
        (directly fetched), are left alone."""
        reg = self._graph._scoped_state.get("__autoshard_constraints__")
        if not reg:
            return pruned
        in_plan = set(pruned)
        by_producer = {}
        for t, cop in reg.items():
            if cop in in_plan:
                continue
            target = alias.get(t, t)
            if target in const_env:
                continue
            if target.op in in_plan:
                by_producer.setdefault(target.op, []).append(cop)
        if not by_producer:
            return pruned
        spliced = []
        for op in pruned:
            spliced.append(op)
            for cop in by_producer.get(op, ()):
                spliced.append(cop)
        return spliced

    def _plan_has_sharding_signals(self, pruned, fed_set) -> bool:
        """Whether a plan is worth sharding-analyzing: it is fed (a
        step-shaped program — the mesh-axis-unused lint is exactly
        right there, sharded or not) or some sharding is configured
        (variable/feed shardings, an explicit constraint, a shard_map).
        Variable-initializer plans and bare state reads have neither,
        and flagging THEM as 'mesh axis unused' under an active mesh
        would be noise on every init run of a correctly-sharded job."""
        if fed_set or self._variable_store.shardings:
            return True
        for op in pruned:
            if op.type in ("ShardingConstraint", "ShardMap"):
                return True
            if op.attrs.get("sharding") is not None:
                return True
            if op.type == "VariableV2":
                vn = op.attrs.get("var_name", op.name)
                reg = self._graph._scoped_state.get(
                    "__vars_by_store_name__", {})
                var = reg.get(vn)
                if var is not None and var.sharding is not None:
                    return True
        return False

    def _plan(self, elements, feeds) -> _CompiledStep:
        import jax

        step = _CompiledStep()
        fed_set: Set[Tensor] = set(feeds)
        target_ops: List[Operation] = []
        fetch_tensors: List[Tensor] = []
        for e in elements:
            if isinstance(e, Operation):
                target_ops.append(e)
            else:
                fetch_tensors.append(e)
                if e not in fed_set:
                    target_ops.append(e.op)
        with monitoring.traceme("session/prune", n_target_ops=len(target_ops)):
            pruned = lowering_mod.prune(target_ops, fed_set)

        # Plan-time graph optimizer: fold/CSE/DCE before lowering (the
        # grappler slot, ref core/common_runtime/constant_folding.cc +
        # core/graph/optimizer_cse.cc). Folded outputs seed the lowering
        # env; CSE'd tensors resolve through the alias map.
        from ..framework import optimizer as graph_opt

        func_plans: Dict[Any, Any] = {}
        with monitoring.traceme("session/optimize", n_pruned_ops=len(pruned)):
            pruned, const_env, alias = graph_opt.optimize_pruned(
                pruned, fed_set, fetch_tensors, func_plans=func_plans)
        step.const_env = const_env
        step.alias = alias
        step.func_plans = func_plans
        # auto-sharding (ISSUE 14): under ConfigProto(auto_shard=True)
        # with a >1-device mesh, the FIRST fed (step-shaped) plan runs
        # the PartitionSpec search over its pruned op list and commits
        # the winner BEFORE lowering/compile — variable + feed
        # shardings plus committing ShardingConstraint cut points
        # (spliced below). Applied once per graph; user-placed specs
        # are fixed seeds the search never overrides.
        pruned = self._maybe_auto_shard(pruned, fed_set, elements)
        pruned = self._splice_commit_constraints(pruned, alias,
                                                 const_env)
        # stf.analysis per-plan checks (cached by plan signature — _plan
        # only runs on executable-cache misses): the variable-hazard
        # detector (RAW/WAR/WAW; SURVEY §5 upgraded to declared effect
        # sets, modes off|warn|raise|auto_deps — auto_deps re-orders the
        # plan to program order, TF auto-control-dependencies) plus, when
        # the session opted in, structural re-verification of the plan.
        from .. import analysis

        a_t0 = time.perf_counter()
        with monitoring.traceme("session/analysis", n_pruned_ops=len(pruned)):
            pruned, plan_diags = analysis.check_plan(
                pruned, alias, mode=self._hazard_mode())
            if self._analysis_mode != "off":
                analysis.verify_ops(pruned, level="structural",
                                    diags=plan_diags)
            # sharding analysis (ISSUE 6): when a mesh is active at plan
            # time, predict per-edge collective bytes + lint the plan's
            # shardings. Cached with the plan (same lifetime as hazards:
            # _plan only runs on executable-cache misses). The analysis
            # is ADVISORY — warnings/notes, never an execution gate — so
            # it runs on a worker thread overlapping lowering + XLA
            # compile instead of stretching the plan's critical path
            # (/stf/analysis/sharding_seconds samples the full cost).
            # Analyzer failures degrade to a log note, never sink a run.
            try:
                from ..parallel import mesh as mesh_mod

                _mesh = mesh_mod.current_mesh()
            except Exception:
                _mesh = None
            if _mesh is not None and getattr(_mesh, "size", 1) > 1 \
                    and self._plan_has_sharding_signals(pruned, fed_set):
                s_t0 = time.perf_counter()
                plan_ops = list(pruned)  # snapshot vs later mutation
                gate = threading.Event()

                def _sharding_worker():
                    from ..platform import tf_logging as _logging

                    # head start for the rest of _plan: a compute-bound
                    # worker launched mid-plan steals GIL slices from
                    # the (pure-Python) staging work it is supposed to
                    # overlap. Waiting a beat lands the analysis inside
                    # the jit trace/compile window, where the GIL is
                    # released for long C++ stretches; join_sharding
                    # opens the gate immediately when a reader waits.
                    gate.wait(1.0)
                    try:
                        rep = analysis.analyze_sharding(
                            graph=self._graph, ops=plan_ops, mesh=_mesh,
                            fetches=fetch_tensors)
                    except Exception as e:  # noqa: BLE001 — advisory
                        _logging.warning(
                            "plan analysis: NOTE "
                            "sharding/analysis-failed: %s: %s",
                            type(e).__name__, e)
                        return
                    step.sharding_report = rep
                    for d in rep.diagnostics:
                        _logging.warning("plan analysis: %s",
                                         d.format())

                th = threading.Thread(target=_sharding_worker,
                                      name="stf_sharding_analysis",
                                      daemon=True)
                step.sharding_thread = th
                step.sharding_gate = gate
                th.start()
                step.sharding_sync_seconds = \
                    time.perf_counter() - s_t0
        analysis.diagnostics.metric_check_seconds.get_cell().add(
            time.perf_counter() - a_t0)
        if plan_diags:
            from ..platform import tf_logging as logging

            rec = _flight_mod.get_recorder()
            if rec.enabled:
                # hazard/lint findings are forensics gold: the last
                # diagnostics before a wedge usually name the culprit
                for d in plan_diags[:20]:
                    rec.record("diagnostic", severity=d.severity,
                               code=d.code, message=d.message[:300],
                               op=d.op_name)
            errs = analysis.errors(plan_diags)
            for d in plan_diags:
                if not d.is_error:
                    logging.warning("plan analysis: %s", d.format())
            if errs and self._analysis_mode == "strict":
                raise errors.InvalidArgumentError(
                    None, None, analysis.format_report(
                        errs, header="plan verification failed:"))
        # numerics-health plane (ISSUE 17; stf.debug.numerics): when the
        # resolved mode is not "off" and this plan is training-shaped (a
        # device op writes a variable), splice NumericSummary taps over
        # gradients/updates/loss (+ numerics_taps activation patterns)
        # and one Pack producing the [T, 4] health tensor. Ops are
        # spliced at plan time (the __autoshard_constraints__ idiom), so
        # they fuse into the step program and ride fused windows —
        # advisory: an instrumentation failure logs, never sinks a plan.
        num_mode = self._numerics_mode()
        if num_mode != "off":
            try:
                from ..debug import numerics as _numerics_mod

                patterns = tuple(getattr(
                    self._config, "numerics_taps", ()) or ())
                pruned, tap_table, health_t = _numerics_mod.instrument_plan(
                    self._graph, pruned, fed_set, fetch_tensors, alias,
                    const_env, patterns=patterns)
                if tap_table:
                    step.numerics = {"mode": num_mode, "taps": tap_table,
                                     "tensor": health_t, "index": None}
                    _numerics_mod.get_plane().set_taps(tap_table)
            except Exception as e:  # noqa: BLE001 — advisory plane
                from ..platform import tf_logging as logging

                logging.warning(
                    "numerics plane: instrumentation failed, plan runs "
                    "uninstrumented: %s: %s", type(e).__name__, e)
        # staging/partitioning starts AFTER the analysis block: the
        # "lower" span must not double-count the "analysis" span. Entered
        # by hand (closed below, once the stages are counted) rather
        # than re-indenting the whole staging pass under a ``with``.
        lower_span = monitoring.traceme("session/lower")
        lower_span.__enter__()

        def _rsv(t):  # resolve through CSE aliases
            return alias.get(t, t)

        # Three stages (replaces the reference's CPU/GPU placement split,
        # ref core/common_runtime/simple_placer.cc):
        #   pre-host  — host sources (queues, readers, var introspection)
        #   device    — ONE jitted XLA program
        #   post-host — host sinks consuming device results (summaries, ...)
        device_ops: List[Operation] = []
        pre_host: List[Operation] = []
        post_host: List[Operation] = []
        host_producers: Set[Tensor] = set()
        has_dev_anc: Set[Operation] = set()
        device_op_set: Set[Operation] = set()
        post_host_set: Set[Operation] = set()
        for op in pruned:
            dev_anc = any(
                (_rsv(t).op in device_op_set or _rsv(t).op in has_dev_anc)
                and _rsv(t) not in fed_set and _rsv(t) not in const_env
                for t in op.inputs) or any(
                c in device_op_set or c in has_dev_anc
                for c in op.control_inputs)
            # string tensors never enter XLA: a Const producing strings is
            # a host source, not a device op (mirrors ref CPU pinning of
            # string kernels in simple_placer.cc)
            is_string_const = (op.type == "Const" and any(
                o.dtype.base_dtype == dtypes_mod.string
                for o in op.outputs))
            if (op.op_def.runs_on_host or is_string_const or
                    _is_host_device(op.device)):
                if dev_anc:
                    post_host.append(op)
                    post_host_set.add(op)
                    has_dev_anc.add(op)
                else:
                    pre_host.append(op)
                host_producers.update(op.outputs)
            else:
                if any(_rsv(t).op in post_host_set for t in op.inputs):
                    raise errors.InvalidArgumentError(
                        None, op,
                        f"Device op {op.name} consumes output of host sink "
                        f"op; use stf.py_func (pure_callback) to re-enter "
                        "the device program.")
                device_ops.append(op)
                device_op_set.add(op)
                if dev_anc:
                    has_dev_anc.add(op)
        # Pre-host ops may only consume feeds, consts, or other host outputs.
        pre_set = set(pre_host)
        for op in pre_host:
            for t in op.inputs:
                t = _rsv(t)
                if (t in fed_set or t in host_producers or t in const_env or
                        t.op.type == "Const" or t.op in pre_set):
                    continue
                raise errors.InvalidArgumentError(
                    None, op,
                    f"Host op {op.name} consumes device tensor {t.name} "
                    "without a device ancestor path — internal staging bug.")
        # Consts consumed by host ops lower on host too.
        const_for_host: List[Operation] = []
        host_all = pre_host + post_host
        host_all_set = set(host_all)
        for op in host_all:
            for t in op.inputs:
                t = _rsv(t)
                if t in const_env:
                    continue  # seeded straight into the host env
                if t.op.type == "Const" and t.op not in host_all_set and \
                        t.op not in const_for_host:
                    const_for_host.append(t.op)
        step.host_plan = const_for_host + pre_host
        step.post_host_plan = post_host
        if self._config is not None and getattr(
                self._config, "log_device_placement", False):
            from ..platform import tf_logging as logging

            for op, stage in ([(o, "host(pre)") for o in step.host_plan]
                              + [(o, "device:TPU") for o in device_ops]
                              + [(o, "host(post)") for o in post_host]):
                logging.info("placement: %s (%s) -> %s", op.name, op.type,
                             stage)
        # Device tensors needed by post-host ops become extra device fetches.
        post_needs: List[Tensor] = []
        seen_pn: Set[Tensor] = set()
        for op in post_host:
            for t in op.inputs:
                t = _rsv(t)
                if t.op in device_op_set and t not in seen_pn:
                    seen_pn.add(t)
                    post_needs.append(t)
        step.post_host_inputs = post_needs
        # inputs of GetSessionHandle must stay raw device arrays in the
        # post-host env (pinning a handle must not force a host transfer)
        step.raw_post_inputs = {
            _rsv(t) for op in post_host if op.type == "GetSessionHandle"
            for t in op.inputs}

        # Boundary: host/feed tensors consumed by device ops.
        boundary: List[Tensor] = []
        seen: Set[Tensor] = set()
        for op in device_ops:
            for t in op.inputs:
                t = _rsv(t)
                if (t in fed_set or t in host_producers) and t not in seen:
                    seen.add(t)
                    boundary.append(t)
        for t in fetch_tensors:
            if t in fed_set and t not in seen:
                seen.add(t)
                boundary.append(t)
        step.feed_tensors = boundary

        # Device fetches: fetch tensors produced by device ops, plus tensors
        # the post-host stage needs (all alias-resolved).
        device_fetches = [_rsv(t) for t in fetch_tensors
                          if _rsv(t).op in device_op_set]
        for t in step.post_host_inputs:
            if t not in device_fetches:
                device_fetches.append(t)
        step.device_fetches = device_fetches
        step.device_ops = device_ops
        # numerics plane: the packed health tensor rides the normal
        # fetch channel (16·T bytes/step — the whole point: no extra
        # device_get, no fused-window split); record its slot so the
        # post-commit observer can find it
        if step.numerics is not None:
            ht = step.numerics["tensor"]
            if ht.op in device_op_set:
                if ht not in device_fetches:
                    device_fetches.append(ht)
                step.numerics["index"] = device_fetches.index(ht)
            else:  # defensive: taps pruned away / host-staged
                step.numerics = None
        # static fetch sizes for the transfer guard (computed once here,
        # not per step; None num_elements = dynamic shape, unguarded)
        step.fetch_nbytes = [
            (t.name, t.shape.num_elements() * t.dtype.base_dtype.size)
            for t in device_fetches
            if t.shape.num_elements() is not None
            and t.dtype.name != "string"]
        # staging/partitioning = the "lower" lifecycle phase (the
        # reference's placement + partitioning ahead of executor build)
        lower_span.set_meta(n_device_ops=len(device_ops),
                            n_host_ops=len(step.host_plan),
                            n_post_host_ops=len(post_host))
        lower_span.__exit__(None, None, None)
        rec = _flight_mod.get_recorder()
        if rec.enabled:
            rec.record("plan", n_pruned=len(pruned),
                       n_device_ops=len(device_ops),
                       n_host_ops=len(step.host_plan),
                       n_post_host_ops=len(post_host),
                       n_diagnostics=len(plan_diags))
        step.has_device_stage = bool(device_ops)
        step.uses_rng = bool(device_ops) and _plan_uses_rng(device_ops)
        if self._memory_budget:
            # device-memory budget admission (stf.telemetry.memory):
            # refuse un-fittable plans BEFORE compile/launch; the
            # estimate is skipped entirely when no budget is set
            self._admit_plan_memory(step, elements, list(feeds))
        if not step.has_device_stage:
            step.jitted = None
            return step

        host_boundary = [t for t in boundary]
        store = self._variable_store

        check_msgs: List[str] = []  # filled at trace time, index-aligned

        plan_alias = step.alias
        plan_consts = step.const_env
        plan_func_plans = step.func_plans

        def step_fn(state, feed_args, rng_root, run_idx):
            import jax.numpy as jnp

            # per-step key derived INSIDE the compiled program: traced
            # once, fused (or DCE'd when no op consumes RNG) — the host
            # passes only the base key and a counter (see _rng_args)
            rng = jax.random.fold_in(rng_root, run_idx)
            ctx = lowering_mod.LoweringContext(state, rng_root=rng,
                                               session=self)
            ctx.alias = plan_alias
            ctx.func_plans = plan_func_plans
            for t, v in plan_consts.items():
                if t.dtype.name != "string":
                    ctx.env[t] = jnp.asarray(v)  # folded at plan time
            for t in host_boundary:
                ctx.env[t] = feed_args[t.name]
            lowering_mod.execute_ops(ctx, device_ops, fed=set(host_boundary))
            fetch_vals = [ctx.env[t] for t in device_fetches]
            check_msgs.clear()  # jit may trace more than once
            check_msgs.extend(m for m, _ in ctx.numeric_checks)
            flags = [f for _, f in ctx.numeric_checks]
            return fetch_vals, ctx.state, flags

        # Donation deletes the pre-step variable buffers. When the step
        # contains CheckNumerics or Assert (both ride the flag channel:
        # the Session raises BEFORE committing state), a failed check
        # must leave the OLD state intact (ref semantics: downstream ops
        # never run), so donation is disabled for those steps —
        # otherwise a check failure would brick the session with
        # deleted arrays.
        has_checks = any(op.type in ("CheckNumerics", "Assert")
                         for op in device_ops)
        # numerics "dump" re-executes the failing step eagerly from the
        # PRE-step state to bisect the first bad op — that state must
        # survive the step, so donation is off. "metrics"/"raise" are
        # post-commit observers and keep the donation fast path.
        if step.numerics is not None and step.numerics["mode"] == "dump":
            has_checks = True
        step.jitted = jax.jit(step_fn,
                              donate_argnums=() if has_checks else (0,))
        step.check_msgs = check_msgs
        return step

    # -- partial run (ref: session.py partial_run; execute-once semantics
    # per handle like DirectSession's partial-run support in
    # core/common_runtime/direct_session.cc) ---------------------------------
    def partial_run_setup(self, fetches, feeds=None):
        handle = f"pr_{len(self._partial_runs)}"
        mapper = _FetchMapper(self._graph, fetches)
        self._partial_runs[handle] = {
            "pending_fetches": set(mapper.elements),
            "env": {},          # Tensor -> computed value, shared across calls
            "executed": set(),  # ops already run under this handle
            "expected_feeds": set(
                self._graph.as_graph_element(f, True, False)
                for f in (feeds or [])),
            "rng": self._next_rng(),
        }
        return handle

    def partial_run(self, handle, fetches, feed_dict=None):
        """Each graph op executes at most ONCE per handle: intermediate
        values persist in the handle's env, so a stateful op (assign_add,
        dequeue) fetched or depended on by two partial_run calls runs only
        the first time. Execution is op-at-a-time eager (the reference's
        executor model) — partial_run is a debugging/streaming API, not the
        jitted hot path."""
        st = self._partial_runs.get(handle)
        if st is None:
            raise errors.InvalidArgumentError(
                None, None, f"Unknown partial_run handle {handle}")
        if feed_dict:
            st["env"].update(self._normalize_feeds(feed_dict))
        mapper = _FetchMapper(self._graph, fetches)
        target_ops: List[Operation] = []
        for e in mapper.elements:
            target_ops.append(e if isinstance(e, Operation) else e.op)
        fed = st["expected_feeds"] | set(
            t for t in st["env"] if isinstance(t, Tensor))
        pruned = lowering_mod.prune(target_ops, fed)
        ctx = lowering_mod.LoweringContext(
            self._variable_store.values, rng_root=st["rng"], session=self)
        ctx.env = st["env"]  # shared: results persist across calls
        to_run = [op for op in pruned if op not in st["executed"]]
        lowering_mod.execute_ops(ctx, to_run, fed=fed)
        st["executed"].update(to_run)
        # commit only the keys THIS handle wrote, under the lock: a
        # wholesale reassignment could resurrect a stale dict and erase
        # a concurrent run()'s committed updates
        with self._lock:
            for name in ctx.written:
                self._variable_store.values[name] = ctx.state[name]
            self._variable_store.sync_ledger()

        values = []
        for e in mapper.elements:
            if isinstance(e, Operation):
                values.append(None)
            else:
                v = ctx.env[e] if e in ctx.env else ctx.value_of(e)
                values.append(np.asarray(v) if e.dtype.name != "string"
                              else v)
        return mapper.rebuild(values)

    def partial_run_release(self, handle):
        self._partial_runs.pop(handle, None)

    # -- make_callable (ref: session.py make_callable) -----------------------
    def make_callable(self, fetches, feed_list=None):
        """Returns a function running ``fetches`` with positional feeds.

        Unlike ``run``, the fetch structure and feed tensors are resolved
        ONCE here; when the compiled step is a pure device program (no
        host stages — the training-loop case), each call goes straight to
        the cached jitted function: no fetch mapping, no feed
        normalization, no plan lookup beyond the first call (the role of
        the reference's ``_Callable`` handle over a prebuilt
        DirectSession executor, ref session.py make_callable)."""
        feed_list = feed_list or []
        feed_ts = [self._graph.as_graph_element(f, True, False)
                   for f in feed_list]
        mapper = _FetchMapper(self._graph, fetches)
        state_box = {"step": None}

        def _slow(*args):
            return self.run(fetches, feed_dict=dict(zip(feed_ts, args)))

        def _adoptable(cached):
            """Fast path only for pure device programs whose inputs all
            come from the feed list AND whose every fetch provably
            resolves from feeds/device-fetches/consts — decided HERE,
            before any hot-path execution, so the hot path never needs a
            fall-back after state has committed."""
            if (cached is None or not cached.has_device_stage
                    or cached.host_plan or cached.post_host_plan):
                return False
            feed_set = set(feed_ts)
            if not all(t in feed_set for t in cached.feed_tensors):
                return False
            dev_set = set(cached.device_fetches)
            for e in mapper.elements:
                if isinstance(e, Operation):
                    continue
                r = cached.alias.get(e, e)
                if not (e in feed_set or r in dev_set
                        or r in cached.const_env):
                    return False
            return True

        def _callable(*args):
            if len(args) != len(feed_ts):
                raise ValueError(f"Expected {len(feed_ts)} feed values")
            step = state_box["step"]
            if step is None:
                out = _slow(*args)  # plan + compile through the full path
                cached = self._cache.get(
                    self._cache_key(mapper.elements, feed_ts))
                if _adoptable(cached):
                    state_box["step"] = cached
                return out
            # ---- hot path ----
            if self._closed:
                raise RuntimeError("Attempted to use a closed Session.")
            import jax

            guard_on = (self._config is not None and
                        getattr(self._config, "transfer_guard", "allow")
                        != "allow")
            feeds = {}
            for t, v in zip(feed_ts, args):
                if isinstance(v, jax.Array):
                    if v.dtype != t.dtype.base_dtype.np_dtype:
                        v = v.astype(t.dtype.base_dtype.np_dtype)
                else:
                    v = np.asarray(v, dtype=t.dtype.base_dtype.np_dtype)
                    if guard_on:
                        self._transfer_guard(t.name, v.nbytes, "feed")
                if not t.shape.is_compatible_with(v.shape):
                    raise ValueError(
                        f"Cannot feed value of shape {v.shape} for tensor "
                        f"{t.name} with shape {t.shape}")
                feeds[t] = v
            if guard_on:
                for name, nbytes in step.fetch_nbytes:
                    self._transfer_guard(name, nbytes, "fetch")
            feed_args = {t.name: self._staged_feed(step, t, feeds[t])
                         for t in step.feed_tensors}
            # same serialization as _run_elements: concurrent callables
            # (or a callable racing sess.run) must not share donated
            # state or drop each other's commits
            with self._lock:
                rng_key, rng_ctr = self._rng_args(consume=step.uses_rng)
                state = self._variable_store.values
                fetch_vals, new_state, check_flags = _call_step_executable(
                    step, state, feed_args, rng_key, rng_ctr)
                if check_flags:
                    flags_np = np.asarray(jax.device_get(check_flags))
                    if flags_np.any():
                        bad = [m for m, f in zip(step.check_msgs,
                                                 flags_np) if f]
                        raise errors.InvalidArgumentError(
                            None, None, "; ".join(bad))
                self._variable_store.values = dict(new_state)
                self._apply_declared_shardings(new_state.keys())
                self._variable_store.sync_ledger()
                step.n_calls += 1
            dev_map = dict(zip(step.device_fetches, fetch_vals))
            values = []
            for e in mapper.elements:
                if isinstance(e, Operation):
                    values.append(None)
                    continue
                r = step.alias.get(e, e)
                if e in feeds:
                    values.append(feeds[e])
                elif r in dev_map:
                    v = dev_map[r]
                    values.append(np.asarray(v)
                                  if e.dtype.name != "string" else v)
                else:  # guaranteed by _adoptable
                    values.append(step.const_env[r])
            return mapper.rebuild(values)

        return _callable


class Session(BaseSession):
    """(ref: python/client/session.py:1176 ``class Session``)."""

    @staticmethod
    def reset(target, containers=None, config=None):
        # Containers are per-session here; nothing global to reset.
        return None


class InteractiveSession(BaseSession):
    """Session that installs itself as default on construction
    (ref: python/client/session.py:1332)."""

    def __init__(self, target="", graph=None, config=None):
        super().__init__(target, graph, config)
        if not hasattr(_default_session_stack, "stack"):
            _default_session_stack.stack = []
        _default_session_stack.stack.append(self)

    def close(self):
        stack = getattr(_default_session_stack, "stack", [])
        if self in stack:
            stack.remove(self)
        super().close()
