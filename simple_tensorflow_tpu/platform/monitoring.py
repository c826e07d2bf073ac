"""stf.monitoring: process-global metrics + lightweight tracing
(ref: tensorflow/core/lib/monitoring/{counter,gauge,sampler,
percentile_sampler}.h, python/eager/monitoring.py).

Two halves, both thread-safe and importable from any layer without
cycles (nothing of stf beyond ``platform.sync``; the tracing half needs
``jax.profiler``):

Metrics — a process-global registry of named metric families. Each
family owns labeled cells, created on demand:

    runs = monitoring.Counter("/stf/session/runs", "session.run calls")
    runs.get_cell().increase_by(1)
    misses = monitoring.Counter("/stf/session/executable_cache/misses",
                                "cache misses", "reason")
    misses.get_cell("new_fetch_feed_signature").increase_by(1)

``export()`` renders the whole registry as a nested dict (stable,
JSON-able), ``to_json()`` dumps it, and ``to_prometheus()`` emits the
Prometheus text exposition format so a scrape endpoint is one
``web.Response(monitoring.to_prometheus())`` away.

Tracing — ``traceme(name, **meta)`` is the program's one span
primitive. It has two listeners, and is free when neither is there (one
thread-local read and one call into the profiler's ``is_enabled``):

- a running ``jax.profiler`` session gets a ``TraceAnnotation`` named
  ``stf/<name>``, on the profiler's clock, beside the device's ops in
  the ``.xplane.pb``. Spans at a layer boundary are named
  ``<layer>/<phase>`` (``session/device_execute``, ``engine/decode``);
- every *active* per-thread trace buffer gets a span dict under the
  phase name. Session.run installs a buffer for the duration of a traced
  run (``RunOptions.SOFTWARE_TRACE``) and drains it into
  ``RunMetadata.step_stats`` — the source of the chrome-trace timeline
  (client/timeline.py).

``telemetry.tracing.span`` is this primitive plus one entry in the
request-tracing ring.
"""

from __future__ import annotations

import bisect
import json
import threading
import time
from typing import Any, Dict, List, Optional, Sequence, Tuple

from jax.profiler import TraceAnnotation as _Annotation

from . import sync as _sync

# true while a jax.profiler session is collecting host spans
_profiling = _Annotation.is_enabled

__all__ = [
    "Counter", "IntGauge", "StringGauge", "BoolGauge",
    "Sampler", "PercentileSampler",
    "ExponentialBuckets", "ExplicitBuckets",
    "export", "to_json", "to_prometheus",
    "get_metric", "unregister", "reset_registry",
    "traceme", "trace_collection", "TraceBuffer", "tracing_active",
    "WindowedRate",
]

_registry_lock = _sync.Lock("monitoring/registry",
                            rank=_sync.RANK_METRICS)
_registry: Dict[str, "Metric"] = {}


# ---------------------------------------------------------------------------
# buckets
# ---------------------------------------------------------------------------

class Buckets:
    """Bucket boundaries for Sampler histograms: ``boundaries[i]`` is the
    inclusive upper edge of bucket i (Prometheus ``le``); a final +inf
    bucket is implicit."""

    def __init__(self, boundaries: Sequence[float]):
        bs = [float(b) for b in boundaries]
        if any(b2 <= b1 for b1, b2 in zip(bs, bs[1:])):
            raise ValueError(f"bucket boundaries must increase: {bs}")
        self.boundaries: List[float] = bs


def ExponentialBuckets(scale: float, growth_factor: float,
                       bucket_count: int) -> Buckets:
    """(ref: monitoring/sampler.h ``Buckets::Exponential``): boundaries
    scale, scale*growth, scale*growth^2, ... — bucket_count edges."""
    if scale <= 0 or growth_factor <= 1 or bucket_count < 1:
        raise ValueError(
            f"ExponentialBuckets(scale={scale}, growth_factor="
            f"{growth_factor}, bucket_count={bucket_count}): need "
            "scale>0, growth_factor>1, bucket_count>=1")
    return Buckets([scale * growth_factor ** i for i in range(bucket_count)])


def ExplicitBuckets(boundaries: Sequence[float]) -> Buckets:
    """(ref: monitoring/sampler.h ``Buckets::Explicit``)."""
    return Buckets(boundaries)


# ---------------------------------------------------------------------------
# cells
# ---------------------------------------------------------------------------

class CounterCell:
    __slots__ = ("_value", "_lock")

    def __init__(self):
        self._value = 0
        self._lock = _sync.leaf_lock("monitoring/cell")

    def increase_by(self, value: int = 1):
        if value < 0:
            raise ValueError(f"Counter can only increase (got {value})")
        with self._lock:
            self._value += int(value)

    def value(self) -> int:
        with self._lock:
            return self._value


class GaugeCell:
    __slots__ = ("_value", "_lock")

    def __init__(self, default):
        self._value = default
        self._lock = _sync.leaf_lock("monitoring/cell")

    def set(self, value):
        with self._lock:
            self._value = value

    def value(self):
        with self._lock:
            return self._value


class SamplerCell:
    """Histogram cell: counts per exponential/explicit bucket + sum."""

    __slots__ = ("_buckets", "_counts", "_sum", "_count", "_min", "_max",
                 "_lock")

    def __init__(self, buckets: Buckets):
        self._buckets = buckets
        self._counts = [0] * (len(buckets.boundaries) + 1)
        self._sum = 0.0
        self._count = 0
        self._min = float("inf")
        self._max = float("-inf")
        self._lock = _sync.leaf_lock("monitoring/cell")

    def add(self, value: float):
        v = float(value)
        # bisect_left: a sample equal to an edge counts at-or-below it
        # (Prometheus ``le`` semantics; matches the reference sampler)
        idx = bisect.bisect_left(self._buckets.boundaries, v)
        with self._lock:
            self._counts[idx] += 1
            self._sum += v
            self._count += 1
            self._min = min(self._min, v)
            self._max = max(self._max, v)

    def value(self) -> Dict[str, Any]:
        """Histogram snapshot; ``buckets`` maps upper-edge -> count (the
        final bucket's edge is +inf)."""
        with self._lock:
            edges = self._buckets.boundaries + [float("inf")]
            return {
                "count": self._count,
                "sum": self._sum,
                "min": self._min if self._count else None,
                "max": self._max if self._count else None,
                "buckets": list(zip(edges, list(self._counts))),
            }


class PercentileSamplerCell:
    """Ring buffer of recent samples -> on-demand percentiles
    (ref: monitoring/percentile_sampler.h; the reference also keeps a
    bounded sample set and computes percentiles at harvest time)."""

    __slots__ = ("_percentiles", "_samples", "_max_samples", "_next",
                 "_sum", "_count", "_lock")

    def __init__(self, percentiles: Sequence[float], max_samples: int):
        self._percentiles = list(percentiles)
        self._samples: List[float] = []
        self._max_samples = max_samples
        self._next = 0
        self._sum = 0.0
        self._count = 0
        self._lock = _sync.leaf_lock("monitoring/cell")

    def add(self, value: float):
        v = float(value)
        with self._lock:
            if len(self._samples) < self._max_samples:
                self._samples.append(v)
            else:
                self._samples[self._next] = v
                self._next = (self._next + 1) % self._max_samples
            self._sum += v
            self._count += 1

    def value(self) -> Dict[str, Any]:
        with self._lock:
            samples = sorted(self._samples)
            count, total = self._count, self._sum
        out: Dict[str, Any] = {"count": count, "sum": total,
                               "percentiles": {}}
        if samples:
            n = len(samples)
            for p in self._percentiles:
                idx = min(n - 1, max(0, int(round(p / 100.0 * (n - 1)))))
                out["percentiles"][p] = samples[idx]
        return out


# ---------------------------------------------------------------------------
# metric families
# ---------------------------------------------------------------------------

def _join_labels(key: Tuple[str, ...]) -> str:
    """Cell key tuple -> export()-dict key. '|' separates label values;
    values containing '|' or '\\' are escaped so distinct tuples never
    collide (``_split_labels`` is the inverse)."""
    return "|".join(v.replace("\\", "\\\\").replace("|", "\\|")
                    for v in key)


def _split_labels(s: str) -> List[str]:
    parts: List[str] = []
    cur: List[str] = []
    i = 0
    while i < len(s):
        c = s[i]
        if c == "\\" and i + 1 < len(s):
            cur.append(s[i + 1])
            i += 2
            continue
        if c == "|":
            parts.append("".join(cur))
            cur = []
            i += 1
            continue
        cur.append(c)
        i += 1
    parts.append("".join(cur))
    return parts


class Metric:
    """A named family of labeled cells. Registering two metrics under one
    name is an error (the reference's AlreadyExists) — except that
    re-creating a family with the identical type/labels returns the
    existing one, so module reloads and test re-imports stay idempotent."""

    metric_type = "Metric"

    def __init__(self, name: str, description: str, *label_names: str):
        self.name = name
        self.description = description
        self.label_names = tuple(label_names)
        self._cells: Dict[Tuple, Any] = {}
        self._lock = _sync.leaf_lock("monitoring/family")
        with _registry_lock:
            existing = _registry.get(name)
            if existing is not None:
                if (type(existing) is not type(self)
                        or existing.label_names != self.label_names
                        or not self._same_shape(existing)):
                    raise ValueError(
                        f"metric {name!r} already registered as "
                        f"{type(existing).__name__}"
                        f"{existing.label_names} with a different "
                        "shape (type/labels/buckets/percentiles) — "
                        "names are process-global")
                # adopt the existing family's cells: same name, same
                # shape -> same metric
                self._cells = existing._cells
                self._lock = existing._lock
            _registry[name] = self

    def _new_cell(self):
        raise NotImplementedError

    def _same_shape(self, existing) -> bool:
        """Subclasses with extra configuration (buckets, percentiles)
        override to veto cell adoption on mismatch."""
        return True

    def get_cell(self, *labels: str):
        if len(labels) != len(self.label_names):
            raise ValueError(
                f"metric {self.name!r} takes {len(self.label_names)} "
                f"label(s) {self.label_names}, got {labels!r}")
        key = tuple(str(v) for v in labels)
        with self._lock:
            cell = self._cells.get(key)
            if cell is None:
                cell = self._cells[key] = self._new_cell()
            return cell

    def cells(self) -> Dict[Tuple, Any]:
        with self._lock:
            return dict(self._cells)

    def snapshot(self) -> Dict[str, Any]:
        return {
            "type": self.metric_type,
            "description": self.description,
            "labels": list(self.label_names),
            "cells": {_join_labels(k): c.value()
                      for k, c in self.cells().items()},
        }


class Counter(Metric):
    """(ref: monitoring/counter.h)."""

    metric_type = "Counter"

    def _new_cell(self):
        return CounterCell()


class IntGauge(Metric):
    """(ref: monitoring/gauge.h ``Gauge<int64>``)."""

    metric_type = "IntGauge"

    def _new_cell(self):
        return GaugeCell(0)


class StringGauge(Metric):
    metric_type = "StringGauge"

    def _new_cell(self):
        return GaugeCell("")


class BoolGauge(Metric):
    metric_type = "BoolGauge"

    def _new_cell(self):
        return GaugeCell(False)


class Sampler(Metric):
    """(ref: monitoring/sampler.h): histogram over fixed buckets."""

    metric_type = "Sampler"

    def __init__(self, name: str, buckets: Buckets, description: str,
                 *label_names: str):
        self.buckets = buckets
        super().__init__(name, description, *label_names)

    def _same_shape(self, existing) -> bool:
        return existing.buckets.boundaries == self.buckets.boundaries

    def _new_cell(self):
        return SamplerCell(self.buckets)


class PercentileSampler(Metric):
    """(ref: monitoring/percentile_sampler.h). Labels are positional
    like every other metric family; percentiles/max_samples are
    keyword-only so ``PercentileSampler(name, desc, "label")`` can never
    silently bind a label name as the percentile list."""

    metric_type = "PercentileSampler"

    def __init__(self, name: str, description: str, *label_names: str,
                 percentiles: Sequence[float] = (25.0, 50.0, 90.0, 99.0),
                 max_samples: int = 1024):
        self.percentiles = list(percentiles)
        self.max_samples = int(max_samples)
        super().__init__(name, description, *label_names)

    def _same_shape(self, existing) -> bool:
        return (existing.percentiles == self.percentiles
                and existing.max_samples == self.max_samples)

    def _new_cell(self):
        return PercentileSamplerCell(self.percentiles, self.max_samples)


# ---------------------------------------------------------------------------
# registry export
# ---------------------------------------------------------------------------

def get_metric(name: str) -> Optional[Metric]:
    with _registry_lock:
        return _registry.get(name)


def unregister(name: str):
    with _registry_lock:
        _registry.pop(name, None)


def reset_registry():
    """Drop every registered family — tests only; library metrics
    re-register on next module import, not after this call."""
    with _registry_lock:
        _registry.clear()


class WindowedRate:
    """Sliding-window event-rate estimator (events/sec over the last
    ``window_s`` seconds), feeding gauge-style metrics whose value must
    reflect CURRENT load, not lifetime averages — the
    ``/stf/serving/qps`` gauge is the canonical user. Thread-safe;
    O(1) amortized per event (per-second coarse buckets, not a
    per-event deque)."""

    __slots__ = ("_window_s", "_lock", "_buckets")

    def __init__(self, window_s: float = 10.0):
        self._window_s = max(1.0, float(window_s))
        self._lock = _sync.leaf_lock("monitoring/windowed_rate")
        self._buckets: Dict[int, int] = {}

    def add(self, n: int = 1, now: Optional[float] = None) -> None:
        now = time.monotonic() if now is None else now
        sec = int(now)
        with self._lock:
            self._buckets[sec] = self._buckets.get(sec, 0) + n
            self._evict(now)

    def _evict(self, now: float) -> None:
        horizon = int(now - self._window_s) - 1
        if len(self._buckets) > self._window_s + 2:
            for sec in [s for s in self._buckets if s <= horizon]:
                del self._buckets[sec]

    def rate(self, now: Optional[float] = None) -> float:
        """Events/sec over the trailing window (0.0 when idle)."""
        now = time.monotonic() if now is None else now
        lo = now - self._window_s
        with self._lock:
            total = sum(c for s, c in self._buckets.items() if s + 1 > lo)
        return total / self._window_s


def export() -> Dict[str, Any]:
    """The whole registry as {metric_name: snapshot} (nested dict of
    plain Python scalars — JSON-able as-is)."""
    with _registry_lock:
        metrics = list(_registry.items())
    return {name: m.snapshot() for name, m in sorted(metrics)}


def to_json(**dumps_kwargs) -> str:
    """Strict-JSON dump of ``export()``: non-finite floats (the +inf
    final bucket edge) become strings, since json.dumps would otherwise
    emit the nonstandard ``Infinity`` token no RFC-8259 parser accepts."""

    def _sanitize(o):
        if isinstance(o, dict):
            return {k: _sanitize(v) for k, v in o.items()}
        if isinstance(o, (list, tuple)):
            return [_sanitize(v) for v in o]
        if isinstance(o, float) and (o != o or o in (float("inf"),
                                                     float("-inf"))):
            return str(o)
        return o

    return json.dumps(_sanitize(export()), default=str, **dumps_kwargs)


def _prom_name(name: str) -> str:
    """Metric-name sanitization for the ``/stf/...`` path style:
    every non-[a-zA-Z0-9_] character becomes ``_``, leading/trailing
    runs are stripped, and a name left empty or starting with a digit
    gets a ``_`` prefix (the exposition format's name grammar is
    ``[a-zA-Z_:][a-zA-Z0-9_:]*``; we never emit ``:`` — it is reserved
    for recording rules)."""
    out = "".join(c if c.isalnum() or c == "_" else "_" for c in name)
    out = out.strip("_")
    if not out:
        return "_"
    if out[0].isdigit():
        out = "_" + out
    return out


def _prom_label_value(v) -> str:
    """Escape per the exposition format: backslash, double quote, and
    newline inside label values."""
    return (str(v).replace("\\", "\\\\").replace('"', '\\"')
            .replace("\n", "\\n"))


def _prom_help(text: str) -> str:
    """HELP escaping: backslash and newline only (quotes stay literal
    in HELP text per the exposition format)."""
    return str(text).replace("\\", "\\\\").replace("\n", "\\n")


def _prom_float(v: float) -> str:
    """Sample-value rendering: finite floats as repr, non-finites as
    the exposition tokens ``+Inf``/``-Inf``/``NaN``."""
    v = float(v)
    if v != v:
        return "NaN"
    if v == float("inf"):
        return "+Inf"
    if v == float("-inf"):
        return "-Inf"
    return repr(v)


def to_prometheus() -> str:
    """Prometheus text exposition format (version 0.0.4). Counters and
    gauges map directly; Samplers map to the native histogram type
    (CUMULATIVE ``_bucket`` series ending in ``le="+Inf"`` whose count
    equals ``_count``); PercentileSamplers map to summary quantiles;
    StringGauges become info-style series (``value="..."`` label,
    sample 1). Iterates the live cells with their tuple label keys, so
    label VALUES — including empty strings and values containing the
    export() separator — round-trip exactly."""
    with _registry_lock:
        metrics = sorted(_registry.items())
    lines: List[str] = []
    for name, m in metrics:
        pname = _prom_name(name)
        labels = m.label_names

        def _labelstr(key: Tuple[str, ...], extra: str = "") -> str:
            parts = [f'{ln}="{_prom_label_value(lv)}"'
                     for ln, lv in zip(labels, key)]
            if extra:
                parts.append(extra)
            return "{" + ",".join(parts) + "}" if parts else ""

        lines.append(f"# HELP {pname} {_prom_help(m.description)}")
        typ = m.metric_type
        cells = sorted(m.cells().items())
        if typ == "Counter":
            lines.append(f"# TYPE {pname} counter")
            for key, cell in cells:
                lines.append(f"{pname}{_labelstr(key)} {cell.value()}")
        elif typ in ("IntGauge", "BoolGauge"):
            lines.append(f"# TYPE {pname} gauge")
            for key, cell in cells:
                lines.append(f"{pname}{_labelstr(key)} {int(cell.value())}")
        elif typ == "StringGauge":
            lines.append(f"# TYPE {pname} gauge")
            for key, cell in cells:
                extra = f'value="{_prom_label_value(cell.value())}"'
                lines.append(f"{pname}{_labelstr(key, extra)} 1")
        elif typ == "Sampler":
            lines.append(f"# TYPE {pname} histogram")
            for key, cell in cells:
                v = cell.value()
                cum = 0
                for edge, count in v["buckets"]:
                    cum += count
                    extra = f'le="{_prom_float(edge)}"'
                    lines.append(
                        f"{pname}_bucket{_labelstr(key, extra)} {cum}")
                lines.append(f"{pname}_sum{_labelstr(key)} "
                             f"{_prom_float(v['sum'])}")
                lines.append(f"{pname}_count{_labelstr(key)} {v['count']}")
        elif typ == "PercentileSampler":
            lines.append(f"# TYPE {pname} summary")
            for key, cell in cells:
                v = cell.value()
                for p, q in v["percentiles"].items():
                    extra = f'quantile="{_prom_float(p / 100.0)}"'
                    lines.append(
                        f"{pname}{_labelstr(key, extra)} {_prom_float(q)}")
                lines.append(f"{pname}_sum{_labelstr(key)} "
                             f"{_prom_float(v['sum'])}")
                lines.append(f"{pname}_count{_labelstr(key)} {v['count']}")
        else:  # unknown family type: emit nothing but the HELP line
            lines.append(f"# TYPE {pname} untyped")
    return "\n".join(lines) + "\n"


# ---------------------------------------------------------------------------
# tracing
# ---------------------------------------------------------------------------

class TraceBuffer:
    """Span sink. Spans are dicts {name, start_s (perf_counter), dur_s,
    tid (OS thread id), meta}. Appends are locked so spawned worker
    threads can share a buffer installed by their parent."""

    def __init__(self):
        self.spans: List[Dict[str, Any]] = []
        self._lock = _sync.leaf_lock("monitoring/trace_buffer")

    def append(self, span: Dict[str, Any]):
        with self._lock:
            self.spans.append(span)

    def drain(self) -> List[Dict[str, Any]]:
        with self._lock:
            out, self.spans = self.spans, []
        return out

    def __len__(self):
        return len(self.spans)


_trace_local = threading.local()


def _sinks() -> List[TraceBuffer]:
    sinks = getattr(_trace_local, "sinks", None)
    if sinks is None:
        sinks = _trace_local.sinks = []
    return sinks


def tracing_active() -> bool:
    return bool(getattr(_trace_local, "sinks", None))


def active_trace_buffers() -> List[TraceBuffer]:
    """The collections installed on the CURRENT thread. Worker threads
    (e.g. stf.data pipeline stages) enter ``trace_collection(buf)`` for
    each of these so their spans land in the parent's trace — sinks are
    per-thread, a spawned thread starts with none."""
    return list(getattr(_trace_local, "sinks", None) or [])


class trace_collection:
    """Install ``buffer`` as an active per-thread span sink for the
    duration of the ``with`` block; nested collections stack (each span
    lands in every active buffer)."""

    def __init__(self, buffer: Optional[TraceBuffer] = None):
        self.buffer = buffer if buffer is not None else TraceBuffer()

    def __enter__(self) -> TraceBuffer:
        _sinks().append(self.buffer)
        return self.buffer

    def __exit__(self, *exc):
        sinks = _sinks()
        if self.buffer in sinks:
            sinks.remove(self.buffer)
        return False


class traceme:
    """The span context-manager (ref: profiler TraceMe). Free when no
    collection is active on this thread and no profiler session runs.
    ``name`` is ``<layer>/<phase>`` at a layer boundary: the profiler
    sees ``stf/<layer>/<phase>``, a collection the phase alone (its
    timeline's rows and tracks are keyed on phases). ``meta`` keys land
    in the span's ``meta`` dict (chrome-trace ``args``) and in the
    annotation's stats."""

    __slots__ = ("name", "meta", "_t0", "_sinks", "_ann")

    def __init__(self, name: str, **meta):
        self.name = name
        self.meta = meta
        self._sinks = None
        self._ann = None

    def __enter__(self):
        sinks = getattr(_trace_local, "sinks", None)
        if sinks:
            self._sinks = list(sinks)
            self._t0 = time.perf_counter()
        if _profiling():
            self._ann = _Annotation("stf/" + self.name, **self.meta)
            self._ann.__enter__()
        return self

    def set_meta(self, **meta):
        """Metadata known only inside the block (how many joined, the
        bucket the model chose)."""
        self.meta.update(meta)
        if self._ann is not None:
            self._ann.set_metadata(**meta)

    def __exit__(self, *exc):
        if self._ann is not None:
            self._ann.__exit__(*exc)
        if self._sinks:
            dur = time.perf_counter() - self._t0
            span = {"name": self.name.partition("/")[2] or self.name,
                    "start_s": self._t0, "dur_s": dur,
                    "tid": threading.get_ident(), "meta": self.meta}
            for s in self._sinks:
                s.append(span)
        return False


# ---------------------------------------------------------------------------
# /stf/sync/* — the lock-witness plane's own metrics. Created HERE (not
# in platform.sync) because sync is stdlib-only — monitoring's own
# locks come from it, so the import can only run this direction. The
# families register at import time (the docs/OBSERVABILITY.md drift
# gate requires it) and the cell-update callables are injected into
# sync, which calls them outside its internal lock with a reentrancy
# guard set.
# ---------------------------------------------------------------------------

_sync_contentions = Counter(
    "/stf/sync/contentions",
    "Contended sync.Lock acquisitions (waits >= 100us)", "lock")
_sync_wait_seconds = Sampler(
    "/stf/sync/contention_wait_seconds",
    ExponentialBuckets(1e-4, 4.0, 10),
    "Seconds spent blocked on contended sync.Lock acquires", "lock")
_sync_potential_deadlocks = Counter(
    "/stf/sync/potential_deadlocks",
    "Lock-order cycles observed by the witness (potential deadlocks, "
    "deduped by cycle)", "cycle")
_sync_rank_violations = Counter(
    "/stf/sync/rank_violations",
    "Acquisitions of a lower-ranked lock while holding a higher-ranked "
    "one", "lock")
_sync_witness_edges = IntGauge(
    "/stf/sync/witness_edges",
    "Distinct lock-order edges in the witness graph")

_sync.bind_metrics(
    contention=lambda lock:
        _sync_contentions.get_cell(lock).increase_by(1),
    wait=lambda lock, s: _sync_wait_seconds.get_cell(lock).add(s),
    cycle=lambda key:
        _sync_potential_deadlocks.get_cell(key).increase_by(1),
    violation=lambda lock:
        _sync_rank_violations.get_cell(lock).increase_by(1),
    edges=lambda n: _sync_witness_edges.get_cell().set(n),
)
