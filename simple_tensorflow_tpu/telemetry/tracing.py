"""Request-scoped tracing: trace ids + an always-on recent-span ring.

``stf.monitoring.traceme`` spans are free unless a profiler session runs
or a per-thread collection is installed — right for the training loop,
wrong for serving, where the question is "what happened to THIS request"
long after it finished. This module adds the serving-side half:

- a ``trace_id`` (16 hex chars) minted at ``ModelServer.predict`` (or
  accepted from the caller, so an upstream gateway's id rides through)
  and propagated via a thread-local scope across the batcher thread,
  ``ExecutionPlan.execute``, and response materialization;
- ``span(...)``: the ``traceme`` primitive that also appends itself, on
  exit, to a bounded process-global ring (one deque append — always on)
  and a ``span`` event to the flight recorder; ``emit_span(...)``
  appends a span closed after the fact (a request's queue wait begins
  on the client's thread and ends on the scheduler's);
- ``chrome_trace(trace_id)``: render the ring (optionally filtered to
  one request) as a chrome-trace JSON string — queue-wait vs batch
  assembly vs device execute vs D2H fetch for a single request, ready
  for ui.perfetto.dev.

A batch-level span carries ``trace_ids`` (every request that rode the
batch); filtering by any one of them finds it.
"""

from __future__ import annotations

import collections
import json
import os
import threading
import time
import uuid
from typing import Any, Dict, List, Optional, Sequence, Union

from . import recorder as _recorder_mod
from ..platform import monitoring as _monitoring
from ..platform import sync as _sync

SPAN_RING_CAPACITY = int(os.environ.get("STF_TELEMETRY_SPANS", "4096"))

_spans: "collections.deque" = collections.deque(
    maxlen=max(64, SPAN_RING_CAPACITY))
# leaf: one append/snapshot per span, the second-highest-rate lock in
# the process; its bodies never acquire (runtime_lint nested-under-leaf)
_spans_lock = _sync.leaf_lock("telemetry/spans")

_local = threading.local()

# span recording on/off (STF_REQUEST_TRACING=0 disables): trace ids
# still mint and propagate — only the ring/recorder appends stop, so a
# minimal-overhead deployment keeps id plumbing for its gateway logs
_enabled = os.environ.get("STF_REQUEST_TRACING", "1") != "0"


def set_enabled(enabled: bool) -> None:
    global _enabled
    _enabled = bool(enabled)


def enabled() -> bool:
    return _enabled


def new_trace_id() -> str:
    return uuid.uuid4().hex[:16]


def current_trace_id() -> Optional[str]:
    """The innermost trace id on this thread (None outside any scope).
    A batch scope (list of ids) reports its first id."""
    ids = getattr(_local, "trace_ids", None)
    if not ids:
        return None
    top = ids[-1]
    return top[0] if isinstance(top, (list, tuple)) and top else (
        top if isinstance(top, str) else None)


def current_trace_ids() -> Optional[List[str]]:
    """All ids of the innermost scope (a batch scope carries one per
    coalesced request); None outside any scope."""
    ids = getattr(_local, "trace_ids", None)
    if not ids:
        return None
    top = ids[-1]
    return list(top) if isinstance(top, (list, tuple)) else [top]


class trace_scope:
    """Install trace id(s) on this thread for the block — spans emitted
    inside (with no explicit id) link to them. Accepts one id or a
    sequence (the batcher's coalesced-batch scope); nests."""

    __slots__ = ("ids",)

    def __init__(self, trace_ids: Union[str, Sequence[str], None]):
        self.ids = trace_ids

    def __enter__(self):
        stack = getattr(_local, "trace_ids", None)
        if stack is None:
            stack = _local.trace_ids = []
        stack.append(self.ids)
        return self.ids

    def __exit__(self, *exc):
        stack = getattr(_local, "trace_ids", None)
        if stack:
            stack.pop()
        return False


def emit_span(name: str, start_s: float, dur_s: float,
              trace_id: Optional[str] = None,
              trace_ids: Optional[Sequence[str]] = None,
              **meta) -> None:
    """Record one closed span into the ring + flight recorder.
    ``start_s`` is perf_counter seconds (same clock Session spans use).
    With neither id given, the current scope's ids are attached."""
    if not _enabled:
        return
    if trace_id is None and trace_ids is None:
        scoped = current_trace_ids()
        if scoped is not None:
            if len(scoped) == 1:
                trace_id = scoped[0]
            else:
                trace_ids = scoped
    # hot-path shape: the ring stores raw tuples; the span DICTS the
    # readers see are built in recent_spans() — per read, not per span
    # (the ISSUE 13 memory-row overhead budget covers this path)
    thread = threading.current_thread().name
    start_s = float(start_s)
    dur_s = float(dur_s)
    item = (name, start_s, dur_s, trace_id,
            tuple(trace_ids) if trace_ids else None,
            threading.get_ident(), thread, meta or None)
    with _spans_lock:
        _spans.append(item)
    rec = _recorder_mod.get_recorder()
    if rec.enabled:
        # span-close breadcrumb (meta stays in the span ring — the
        # flight event carries only the fields a postmortem greps
        # for); raw append reusing this span's clock/thread values —
        # the close instant on the perf_counter clock is start + dur
        rec._append(time.time(), start_s + dur_s, "span", thread,
                    {"name": name, "dur_s": dur_s,
                     "trace_id": trace_id or
                     (trace_ids[0] if trace_ids else None)})


class span(_monitoring.traceme):
    """``monitoring.traceme`` plus one entry in the ring on exit (always
    on, where the primitive alone needs a listener). ``name`` is what
    the profiler and a collection see, ``ring`` the entry's name where
    /tracez has long known the span by another. Keep it off per-op hot
    paths; per-request/per-batch is its grain. ``dur_s`` holds the
    span's seconds once it has closed."""

    __slots__ = ("ring", "trace_id", "trace_ids", "start_s", "dur_s")

    def __init__(self, name: str, ring: Optional[str] = None,
                 trace_id: Optional[str] = None,
                 trace_ids: Optional[Sequence[str]] = None, **meta):
        super().__init__(name, **meta)
        self.ring = ring or name
        self.trace_id = trace_id
        self.trace_ids = trace_ids

    def __enter__(self):
        self.start_s = time.perf_counter()
        return super().__enter__()

    def __exit__(self, *exc):
        super().__exit__(*exc)
        self.dur_s = time.perf_counter() - self.start_s
        emit_span(self.ring, self.start_s, self.dur_s,
                  trace_id=self.trace_id, trace_ids=self.trace_ids,
                  **self.meta)
        return False


def _matches(s: Dict[str, Any], trace_id: str) -> bool:
    return s.get("trace_id") == trace_id or \
        (s.get("trace_ids") and trace_id in s["trace_ids"])


def _span_dict(item) -> Dict[str, Any]:
    name, start_s, dur_s, trace_id, trace_ids, tid, thread, meta = item
    return {"name": name, "start_s": start_s, "dur_s": dur_s,
            "trace_id": trace_id,
            "trace_ids": list(trace_ids) if trace_ids else None,
            "tid": tid, "thread": thread, "meta": meta}


def recent_spans(n: Optional[int] = None,
                 trace_id: Optional[str] = None) -> List[Dict[str, Any]]:
    """Snapshot of the span ring (oldest first), optionally filtered to
    one request's linked spans."""
    with _spans_lock:
        out = [_span_dict(it) for it in _spans]
    if trace_id is not None:
        out = [s for s in out if _matches(s, trace_id)]
    return out[-n:] if n else out


def clear_spans() -> None:
    with _spans_lock:
        _spans.clear()


def chrome_trace(trace_id: Optional[str] = None,
                 spans: Optional[List[Dict[str, Any]]] = None) -> str:
    """Render recent spans (or one request's linked spans) as a
    chrome-trace JSON string. Tracks are the emitting threads;
    timestamps are relative to the earliest span."""
    spans = recent_spans(trace_id=trace_id) if spans is None else spans
    events: List[Dict[str, Any]] = [
        {"name": "process_name", "ph": "M", "pid": 0,
         "args": {"name": ("stf request " + trace_id) if trace_id
                  else "stf.telemetry spans"}}]
    if not spans:
        return json.dumps({"traceEvents": events,
                           "displayTimeUnit": "ms"})
    base = min(s["start_s"] for s in spans)
    tids: Dict[int, int] = {}
    for s in spans:
        tid = tids.setdefault(s["tid"], len(tids))
    for os_tid, tid in tids.items():
        name = next((s["thread"] for s in spans if s["tid"] == os_tid),
                    f"thread {os_tid}")
        events.append({"name": "thread_name", "ph": "M", "pid": 0,
                       "tid": tid, "args": {"name": name}})
    for s in spans:
        args = dict(s.get("meta") or {})
        if s.get("trace_id"):
            args["trace_id"] = s["trace_id"]
        if s.get("trace_ids"):
            args["trace_ids"] = ",".join(s["trace_ids"])
        events.append({
            "name": s["name"], "cat": "telemetry", "ph": "X",
            "ts": (s["start_s"] - base) * 1e6,
            "dur": max(s["dur_s"] * 1e6, 0.1),
            "pid": 0, "tid": tids[s["tid"]],
            "args": {k: str(v) for k, v in args.items()},
        })
    return json.dumps({"traceEvents": events, "displayTimeUnit": "ms"})
