"""Timeline: chrome-trace export (ref: tensorflow/python/client/timeline.py,
core/common_runtime/step_stats_collector.cc).

The reference assembles StepStats from per-kernel timestamps; with XLA the
per-op timeline lives in the profiler. This module provides the
reference's Timeline class over our RunMetadata / step_stats dict —
traced runs (``RunOptions.SOFTWARE_TRACE``) yield one track per
lifecycle stage (planning / host / device), loadable in Perfetto or
chrome://tracing. For the device's own timeline run under
``jax.profiler.start_trace`` (or ``ProfilerHook(use_jax_profiler=True)``):
the program's ``stf/...`` spans land in that trace beside the device ops.
"""

from __future__ import annotations

import json
import time


class Timeline:
    """(ref: timeline.py:308 ``class Timeline``). Accepts a step_stats
    dict (``RunMetadata.step_stats``) or a RunMetadata itself (pulls
    ``cost_graph`` for ``show_memory`` counter tracks)."""

    _PID = 0

    def __init__(self, step_stats, graph=None, cost_graph=None):
        if hasattr(step_stats, "step_stats"):  # a RunMetadata
            if cost_graph is None:
                cost_graph = getattr(step_stats, "cost_graph", None)
            step_stats = step_stats.step_stats
        self._step_stats = step_stats or {}
        self._cost_graph = cost_graph or {}
        self._events = []
        self._build()

    def _metadata(self, name, args, tid=None):
        ev = {"name": name, "ph": "M", "pid": self._PID, "args": args}
        if tid is not None:
            ev["tid"] = tid
        return ev

    def _build(self):
        stats = self._step_stats
        t0 = stats.get("start_us", 0)
        # process/thread naming metadata: Perfetto and chrome://tracing
        # group tracks by these (ref: timeline.py _emit_pid/_emit_tid)
        pname = "stf.Session run"
        window = stats.get("window_steps")
        if window:
            # fused run_steps trace (ProfilerHook annotation): the whole
            # timeline covers global steps [a, b] as ONE device window
            pname = f"stf.Session run_steps[{window[0]}..{window[1]}]"
        self._events.append(self._metadata(
            "process_name", {"name": pname}))
        thread_names = dict(stats.get("thread_names", {}))
        nodes = stats.get("nodes", [])
        for tid in sorted({n.get("tid", 0) for n in nodes}
                          | {int(t) for t in thread_names}):
            name = thread_names.get(tid, thread_names.get(str(tid),
                                                          f"track {tid}"))
            self._events.append(self._metadata(
                "thread_name", {"name": name}, tid=tid))
        for i, node in enumerate(nodes):
            ev = {
                "name": node.get("name", f"op{i}"),
                "cat": "Op",
                "ph": "X",
                "ts": node.get("start_us", t0),
                "dur": node.get("dur_us", 1),
                "pid": self._PID,
                "tid": node.get("tid", 0),
            }
            if node.get("args"):
                ev["args"] = dict(node["args"])
            self._events.append(ev)
        if not nodes and "wall_time_s" in stats:
            self._events.append({
                "name": "session_run", "cat": "Step", "ph": "X",
                "ts": 0, "dur": stats["wall_time_s"] * 1e6,
                "pid": self._PID, "tid": 0})

    def _memory_events(self):
        """Counter events from the executable's memory analysis
        (RunMetadata.cost_graph["memory"]): a flat peak-bytes track over
        the device-execute span — the allocator-level per-op curve of
        the reference lives in XLA, not here."""
        mem = self._cost_graph.get("memory") or {}
        peak = mem.get("peak_bytes")
        if not peak:
            return []
        dev = [n for n in self._step_stats.get("nodes", [])
               if n.get("name") == "device_execute"]
        # span ALL device-execute nodes: the executable's peak holds for
        # each of them, not just the first
        start = min((n["start_us"] for n in dev), default=0)
        end = max((n["start_us"] + n["dur_us"] for n in dev), default=1)
        track = "device memory (peak bytes)"
        return [
            {"name": track, "ph": "C", "pid": self._PID, "ts": start,
             "args": {"bytes": int(peak)}},
            {"name": track, "ph": "C", "pid": self._PID, "ts": end,
             "args": {"bytes": 0}},
        ]

    def _ledger_events(self):
        """Counter events from the HBM ledger's bytes-over-time samples
        (``step_stats["memory_samples"]`` — traced ``run_steps`` windows
        record them from stf.telemetry.memory): live device bytes as a
        chrome counter series next to the op tracks."""
        samples = self._step_stats.get("memory_samples") or []
        track = "device memory (ledger live bytes)"
        return [{"name": track, "ph": "C", "pid": self._PID,
                 "ts": s["t_us"], "args": {"bytes": int(s["bytes"])}}
                for s in samples]

    def generate_chrome_trace_format(self, show_dataflow=True,
                                     show_memory=False):
        events = list(self._events)
        if show_memory:
            events.extend(self._memory_events())
            events.extend(self._ledger_events())
        return json.dumps({"traceEvents": events,
                           "displayTimeUnit": "ms"})


def predicted_vs_measured(fetches, feeds=(), measured_seconds=None):
    """Static cost-model prediction next to a measured step time.
    Moved to framework/cost_model.py (the model owns its own
    verification); kept here as a re-export for existing callers."""
    from ..framework import cost_model

    return cost_model.predicted_vs_measured(
        fetches, feeds=feeds, measured_seconds=measured_seconds)
