"""Continuous/dynamic request batcher for stf.serving.

(ref: tensorflow_serving/batching/basic_batch_scheduler.h — requests
enqueue individually, a scheduler thread coalesces them into batches
closed by size or timeout; tensorflow_serving/batching/
batching_session.cc pads closed batches to allowed_batch_sizes.)

The admission queue is a bounded :class:`~..data.pipeline.RingBuffer`
(the PR 5 stage-decoupling engine — same backpressure, close, and
timed-get semantics the input pipeline runs on). One batcher thread per
servable signature drains it:

- a batch closes at ``max_batch_size`` requests OR ``batch_timeout_ms``
  after its first request arrived, whichever is first;
- requests whose deadline expired while queued are completed with a
  structured ``DeadlineExceededError`` and EXCLUDED — an expired
  request never stalls or poisons the batch it would have ridden;
- live requests are stacked row-wise, padded up to the policy bucket
  (``repeat`` pads with copies of the last row so no NaN/denormal
  garbage changes device timing; ``zero`` pads with zeros), and handed
  to the execute function (ModelServer: ``ExecutionPlan.execute`` with
  ``as_futures=True``);
- each request's :class:`ServeFuture` resolves to its row slice of the
  batch outputs. Materialization is lazy through the PR 4
  ``FetchFuture`` handle: the batcher thread only *dispatches* the
  batch — the device-to-host transfer happens when the first client
  touches its result, so batch N+1 coalesces while batch N executes.

Metrics: the ``/stf/serving/*`` family (docs/OBSERVABILITY.md).

Generative workloads batch at a different altitude: one request is
hundreds of decode steps, so ``serving/generative.py`` generalizes
this scheduler to TOKEN-level continuous batching — the same admission
RingBuffer + ``_QueueStats`` metrics adapter + deadline contract, but
``BatchingPolicy.bucket_for`` consulted once per token over the live
sequence set (see :class:`~.policy.DecodePolicy`), with cache slots
joining/leaving mid-decode instead of requests joining/leaving a
single coalesced batch.
"""

from __future__ import annotations

import threading
import time
from typing import Any, Callable, Dict, List, Optional

import numpy as np

from ..data.pipeline import _DONE, TIMED_OUT, RingBuffer
from ..framework import errors
from ..platform import monitoring
from ..platform import sync as _sync
from ..telemetry import recorder as _flight_mod
from ..telemetry import tracing as _req_tracing
from ..telemetry import watchdog as _watchdog_mod

# ---------------------------------------------------------------------------
# metrics (process-global; registration is idempotent)
# ---------------------------------------------------------------------------

_metric_requests = monitoring.Counter(
    "/stf/serving/requests",
    "Serving requests by final outcome (ok | deadline_exceeded | error | "
    "rejected | cancelled | invalid)", "model", "outcome")
_metric_queue_depth = monitoring.IntGauge(
    "/stf/serving/queue_depth",
    "Requests currently waiting in a model's admission queue", "model")
_metric_queue_stall = monitoring.Counter(
    "/stf/serving/queue_stall_micros",
    "Microseconds spent blocked on the admission queue: produce = "
    "submitters waiting for space (backpressure), consume = the batcher "
    "waiting for requests", "model", "kind")
_metric_batches = monitoring.Counter(
    "/stf/serving/batches", "Batches executed", "model")
_metric_batch_size = monitoring.Sampler(
    "/stf/serving/batch_size",
    monitoring.ExponentialBuckets(1.0, 2.0, 12),
    "Live (unpadded) requests per executed batch", "model")
_metric_batch_fill = monitoring.Sampler(
    "/stf/serving/batch_fill",
    monitoring.ExplicitBuckets(
        [0.125, 0.25, 0.375, 0.5, 0.625, 0.75, 0.875, 1.0]),
    "Live-request fraction of the padded bucket each batch ran at "
    "(1.0 = no padding waste)", "model")
_metric_latency = monitoring.PercentileSampler(
    "/stf/serving/request_latency_seconds",
    "Per-request seconds from admission to response dispatch (result "
    "materialization excluded — responses are lazy FetchFutures)",
    "model", percentiles=(50.0, 90.0, 99.0), max_samples=4096)
_metric_qps = monitoring.IntGauge(
    "/stf/serving/qps",
    "Requests completed OK per second over a trailing 10 s window",
    "model")
_metric_e2e_latency = monitoring.Sampler(
    "/stf/serving/request_e2e_seconds",
    monitoring.ExponentialBuckets(1e-4, 2.0, 22),
    "Per-request seconds from admission to completion, labeled by final "
    "outcome (ok = response dispatched; failures sample at rejection)",
    "model", "outcome")


_metric_queue_wait = monitoring.Sampler(
    "/stf/serving/queue_wait_seconds",
    monitoring.ExponentialBuckets(1e-4, 2.0, 22),
    "Per-request seconds from submission to admission (the batch's "
    "close, or the generative engine taking the request up)", "model")


def record_queue_wait(model: str, request, now: float):
    """The queue-wait leg of one request: the sampler and the request's
    ``serving_queue_wait`` ring span. The one span closed after the fact
    (it begins on the client's thread), hence ``emit_span``."""
    wait = now - request.t_enqueue
    _metric_queue_wait.get_cell(model).add(wait)
    _req_tracing.emit_span("serving_queue_wait", request.t_enqueue, wait,
                           trace_id=request.trace_id, model=model)


class _QueueStats:
    """RingBuffer stats adapter reporting into /stf/serving/* instead of
    the /stf/data/* family (duck-typed to data.pipeline.StageStats:
    the ring only touches ``occupancy`` and ``stall``)."""

    __slots__ = ("occupancy", "_produce", "_consume")

    def __init__(self, model: str):
        self.occupancy = _metric_queue_depth.get_cell(model)
        self._produce = _metric_queue_stall.get_cell(model, "produce")
        self._consume = _metric_queue_stall.get_cell(model, "consume")

    def stall(self, kind: str, seconds: float):
        us = int(seconds * 1e6)
        if us <= 0:
            return
        (self._produce if kind == "produce" else
         self._consume).increase_by(us)


class _BatchOutputs:
    """One executed batch's outputs, shared by its requests. Values are
    FetchFutures (lazy device handles) or arrays; ``row`` materializes
    on first touch (FetchFuture.result is thread-safe and caches the
    host copy, so N requests share ONE device-to-host transfer). The
    first touch emits the batch's ``serving_fetch`` telemetry span —
    the D2H leg of every riding request's trace."""

    __slots__ = ("_outputs", "_model", "_trace_ids", "_lock", "_fetched")

    def __init__(self, outputs: Dict[str, Any], model: str = "",
                 trace_ids: Optional[List[str]] = None):
        self._outputs = outputs
        self._model = model
        self._trace_ids = trace_ids
        self._lock = _sync.Lock("serving/batch_outputs",
                                rank=_sync.RANK_STATE)
        self._fetched = False

    def row(self, index: int) -> Dict[str, np.ndarray]:
        if not self._fetched:
            with self._lock:
                if not self._fetched:
                    with _req_tracing.span(
                            "serving/fetch", ring="serving_fetch",
                            trace_ids=self._trace_ids, model=self._model):
                        self._outputs = {name: np.asarray(v) for name, v
                                         in self._outputs.items()}
                    self._fetched = True
        return {name: np.asarray(v)[index]
                for name, v in self._outputs.items()}


class ServeFuture:
    """Async response handle for one serving request.

    Resolves when the batcher dispatches (or fails) the batch carrying
    the request; ``result()`` then materializes this request's row of
    the batch outputs — blocking on the device only at that point."""

    __slots__ = ("_event", "_batch", "_index", "_exc", "_model",
                 "trace_id")

    def __init__(self, model: str, trace_id: Optional[str] = None):
        self._event = threading.Event()
        self._batch: Optional[_BatchOutputs] = None
        self._index = -1
        self._exc: Optional[BaseException] = None
        self._model = model
        # the request's telemetry trace id (docs/OBSERVABILITY.md):
        # telemetry.chrome_trace(fut.trace_id) renders its linked spans
        self.trace_id = trace_id

    # -- producer side (batcher) --------------------------------------------
    def _set_result(self, batch: _BatchOutputs, index: int):
        self._batch = batch
        self._index = index
        self._event.set()

    def _set_exception(self, exc: BaseException):
        self._exc = exc
        self._event.set()

    # -- consumer side -------------------------------------------------------
    def done(self) -> bool:
        return self._event.is_set()

    def exception(self, timeout: Optional[float] = None):
        """The request's failure (None on success); blocks until the
        request resolves."""
        if not self._event.wait(timeout):
            raise errors.DeadlineExceededError(
                None, None,
                f"serving response for model {self._model!r} not ready "
                f"within {timeout}s")
        return self._exc

    def result(self, timeout: Optional[float] = None
               ) -> Dict[str, np.ndarray]:
        """This request's outputs ({output_key: np.ndarray row});
        raises the per-request error (DeadlineExceededError for an
        expired deadline) instead when the request failed."""
        exc = self.exception(timeout)
        if exc is not None:
            raise exc
        return self._batch.row(self._index)

    def __repr__(self):
        state = ("pending" if not self.done()
                 else "failed" if self._exc is not None else "done")
        return f"<ServeFuture {self._model} {state}>"


class ServeRequest:
    """One admitted request: validated per-example input rows, the
    response future, and an absolute deadline (perf_counter seconds;
    None = no deadline)."""

    __slots__ = ("inputs", "future", "deadline", "t_enqueue", "trace_id")

    def __init__(self, inputs: Dict[str, np.ndarray], future: ServeFuture,
                 deadline: Optional[float] = None,
                 trace_id: Optional[str] = None):
        self.inputs = inputs
        self.future = future
        self.deadline = deadline
        self.t_enqueue = time.perf_counter()
        self.trace_id = trace_id if trace_id is not None \
            else getattr(future, "trace_id", None)

    def expired(self, now: Optional[float] = None) -> bool:
        return self.deadline is not None and \
            (now if now is not None else time.perf_counter()) > self.deadline


class ContinuousBatcher:
    """One admission queue + batcher thread for one servable signature.

    ``execute_fn(batch_inputs, bucket) -> {output_key: array-like}``
    runs the padded batch (ModelServer passes the signature's
    ``ExecutionPlan.execute`` with futures on); outputs must keep the
    batch dim first so row ``i`` belongs to live request ``i``.
    """

    def __init__(self, name: str,
                 execute_fn: Callable[[Dict[str, np.ndarray], int],
                                      Dict[str, Any]],
                 policy):
        self.name = name
        self._execute_fn = execute_fn
        self._policy = policy
        self._queue = RingBuffer(policy.max_queue_depth,
                                 stats=_QueueStats(name))
        self._qps = monitoring.WindowedRate(10.0)
        self._qps_gauge = _metric_qps.get_cell(name)
        self._latency = _metric_latency.get_cell(name)
        # trailing average batch-execute seconds -> watchdog deadline
        self._exec_ewma: Optional[float] = None
        self._closed = False
        self._thread = threading.Thread(
            target=self._loop, name=f"stf_serving_batcher_{name}",
            daemon=True)
        self._thread.start()

    @property
    def closed(self) -> bool:
        return self._closed

    def queue_depth(self) -> int:
        return len(self._queue)

    def refresh_qps(self) -> int:
        """Recompute the /stf/serving/qps gauge from the trailing
        window RIGHT NOW. The batcher refreshes it on every completed
        batch; readers (ModelServer.stats) call this so an idle server
        reports 0 instead of the last batch's stale rate."""
        rate = int(self._qps.rate())
        self._qps_gauge.set(rate)
        return rate

    # -- admission ------------------------------------------------------------
    def submit(self, request: ServeRequest) -> ServeFuture:
        """Admit one request. A full queue blocks the submitter
        (backpressure) until space frees, the request's deadline
        expires, or the server closes — the latter two complete the
        future with a structured error instead of admitting."""
        fut = request.future
        if self._closed:
            self._reject(request, "cancelled", errors.UnavailableError(
                None, None,
                f"model {self.name!r}: server is shut down"))
            return fut
        timeout = None
        if request.deadline is not None:
            timeout = max(request.deadline - time.perf_counter(), 0.0)
        if not self._queue.put(request, timeout=timeout):
            if self._queue.closed:
                self._reject(request, "cancelled",
                             errors.UnavailableError(
                                 None, None,
                                 f"model {self.name!r}: server is shut "
                                 "down"))
            else:
                self._reject(request, "rejected",
                             errors.DeadlineExceededError(
                                 None, None,
                                 f"model {self.name!r}: request deadline "
                                 "expired while waiting for admission "
                                 "(queue full — backpressure)"))
            return fut
        return fut

    def _reject(self, request: ServeRequest, outcome: str,
                exc: BaseException):
        _metric_requests.get_cell(self.name, outcome).increase_by(1)
        _metric_e2e_latency.get_cell(self.name, outcome).add(
            time.perf_counter() - request.t_enqueue)
        request.future._set_exception(exc)

    # -- batching loop --------------------------------------------------------
    def _loop(self):
        pol = self._policy
        while True:
            first = self._queue.get()
            if first is _DONE:
                return
            batch: List[ServeRequest] = [first]
            t_close = time.perf_counter() + pol.batch_timeout_ms / 1000.0
            drained = False
            # burst drain: whatever is already queued joins in one lock
            # acquisition (closed-loop load refills the queue in bursts)
            batch.extend(self._queue.get_available(
                pol.max_batch_size - 1))
            while len(batch) < pol.max_batch_size:
                remaining = t_close - time.perf_counter()
                if remaining <= 0:
                    break
                nxt = self._queue.get(timeout=remaining)
                if nxt is TIMED_OUT:
                    break
                if nxt is _DONE:
                    drained = True
                    break
                batch.append(nxt)
                batch.extend(self._queue.get_available(
                    pol.max_batch_size - len(batch)))
            try:
                self._run_batch(batch)
            except BaseException as e:  # noqa: BLE001 — deliver, never die
                # a batching failure (e.g. ragged dynamic-dim rows that
                # cannot stack) fails THIS batch's requests; the batcher
                # thread must survive for the next batch
                _flight_mod.get_recorder().on_error(
                    e, where="serving_batch", model=self.name)
                for r in batch:
                    if not r.future.done():
                        self._reject(r, "error", e)
            if drained:
                return

    def _run_batch(self, batch: List[ServeRequest]):
        now = time.perf_counter()
        live: List[ServeRequest] = []
        expired = 0
        for r in batch:
            if r.expired(now):
                # satellite (ISSUE 7): an expired deadline is a
                # structured per-request error — the batch runs on
                # without it instead of stalling on a dead client
                expired += 1
                self._reject(r, "deadline_exceeded",
                             errors.DeadlineExceededError(
                                 None, None,
                                 f"model {self.name!r}: request deadline "
                                 "(RunOptions.timeout_in_ms) expired "
                                 "after "
                                 f"{now - r.t_enqueue:.3f}s in the "
                                 "admission queue"))
            else:
                live.append(r)
        if not live:
            return
        k = len(live)
        bucket = self._policy.bucket_for(k)
        pad = bucket - k
        trace_ids = [r.trace_id for r in live if r.trace_id]
        # queue-wait leg of each riding request's trace (ISSUE 8): one
        # span per request, admission -> batch close
        for r in live:
            record_queue_wait(self.name, r, now)
        with _req_tracing.span("serving/batch_assemble",
                               ring="serving_batch_assemble",
                               trace_ids=trace_ids, model=self.name,
                               live=k, bucket=bucket):
            feeds: Dict[str, np.ndarray] = {}
            for name in live[0].inputs:
                stacked = np.stack([r.inputs[name] for r in live])
                if pad:
                    block = (np.repeat(stacked[-1:], pad, axis=0)
                             if self._policy.pad_mode == "repeat" else
                             np.zeros((pad,) + stacked.shape[1:],
                                      dtype=stacked.dtype))
                    stacked = np.concatenate([stacked, block], axis=0)
                feeds[name] = stacked
        # wedge watchdog: a batch 10x past the trailing average is a
        # hang; first batches (no history) are exempt
        wd_deadline = _watchdog_mod.deadline_for(self._exec_ewma)
        wd_token = _watchdog_mod.get_watchdog().arm(
            "serving_batch", wd_deadline, model=self.name,
            live=k, bucket=bucket) if wd_deadline else None
        try:
            with _req_tracing.span("serving/batch_execute",
                                   ring="serving_batch_execute",
                                   trace_ids=trace_ids, model=self.name,
                                   live=k, bucket=bucket) as sp, \
                    _req_tracing.trace_scope(trace_ids):
                outputs = self._execute_fn(feeds, bucket)
        except BaseException as e:  # noqa: BLE001 — delivered per request
            _flight_mod.get_recorder().on_error(
                e, where="serving_batch_execute", model=self.name,
                live=k, bucket=bucket)
            for r in live:
                self._reject(r, "error", e)
            return
        finally:
            _watchdog_mod.get_watchdog().disarm(wd_token)
        exec_dur = sp.dur_s
        done_t = sp.start_s + exec_dur
        self._exec_ewma = exec_dur if self._exec_ewma is None else \
            0.7 * self._exec_ewma + 0.3 * exec_dur
        _metric_batches.get_cell(self.name).increase_by(1)
        _metric_batch_size.get_cell(self.name).add(float(k))
        _metric_batch_fill.get_cell(self.name).add(k / bucket)
        rec = _flight_mod.get_recorder()
        if rec.enabled:
            # batcher decision record: why this batch closed at this
            # size, and what it cost (the forensics a latency SLO
            # post-mortem starts from)
            rec.record("serving_batch", model=self.name, live=k,
                       bucket=bucket, expired=expired,
                       exec_s=round(exec_dur, 6),
                       queue_wait_max_s=round(
                           max(now - r.t_enqueue for r in live), 6))
        shared = _BatchOutputs(outputs, model=self.name,
                               trace_ids=trace_ids)
        ok = _metric_requests.get_cell(self.name, "ok")
        e2e = _metric_e2e_latency.get_cell(self.name, "ok")
        for i, r in enumerate(live):
            r.future._set_result(shared, i)
            self._latency.add(done_t - r.t_enqueue)
            e2e.add(done_t - r.t_enqueue)
        ok.increase_by(k)
        self._qps.add(k)
        self._qps_gauge.set(int(self._qps.rate()))

    # -- lifecycle ------------------------------------------------------------
    def close(self, timeout: float = 10.0):
        """Close admission and drain: queued requests still execute;
        the batcher thread exits once the queue reports drained."""
        self._closed = True
        self._queue.close()
        if self._thread.is_alive() and \
                threading.current_thread() is not self._thread:
            _flight_mod.checked_join(self._thread, timeout,
                                     f"ContinuousBatcher.close({self.name})")
