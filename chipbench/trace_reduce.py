"""From a profiler trace (.xplane.pb) to busy/idle, kernel time and gaps.

The reduction works on plain event lists so that it can be checked on a
small hand-built trace (chipbench/tests/data/): ``load_xplane`` is the only
function that touches the profiler's file format.

An event is ``(name, start_ns, dur_ns, detail)``. Device events come from
the ``XLA Ops`` line of each ``/device:TPU:<n>`` plane (one event per
executed HLO op or custom call); host events from every line of the
``/host:CPU`` plane (TraceMe spans, among them the program's
``stf/<layer>/<phase>`` and the harness's own ``chipbench:<label>``
annotations).
"""

from __future__ import annotations

import glob
import os
import re

SPAN_PREFIX = "chipbench:"
PROGRAM_PREFIX = "stf/"
WINDOW_SPAN = SPAN_PREFIX + "window"


def load_xplane(trace_dir):
    """Read the newest .xplane.pb under ``trace_dir`` into
    ``{"device": {plane: [event]}, "host": [event], "lines": {...}}``."""
    from jax.profiler import ProfileData

    paths = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    data = ProfileData.from_file(paths[-1])
    device, host, lines = {}, [], {}
    for plane in data.planes:
        lines[plane.name] = [ln.name for ln in plane.lines]
        if plane.name.startswith("/device:TPU:"):
            for ln in plane.lines:
                if ln.name == "XLA Ops":
                    device[plane.name] = [
                        (ev.name, int(ev.start_ns), int(ev.duration_ns),
                         _detail(ev)) for ev in ln.events]
        elif plane.name.startswith("/host:"):
            for ln in plane.lines:
                host.extend((ev.name, int(ev.start_ns), int(ev.duration_ns),
                             ln.name) for ev in ln.events)
    return {"device": device, "host": host, "lines": lines,
            "bytes": os.path.getsize(paths[-1])}


def _detail(ev):
    """The op's long name / HLO text where the profiler recorded one: the
    kernel patterns match against ``name + ' ' + detail``."""
    try:
        stats = dict(ev.stats)
    except Exception:  # noqa: BLE001 — a stat the bindings cannot decode
        return ""
    for key in ("long_name", "hlo_op", "tf_op", "name"):
        if key in stats and isinstance(stats[key], str):
            return stats[key]
    return ""


def window_of(host_events, span=WINDOW_SPAN):
    """(start_ns, end_ns) of the harness's window annotation."""
    for name, start, dur, _ in host_events:
        if name == span:
            return start, start + dur
    raise ValueError(f"trace holds no {span!r} annotation")


def clip(events, window):
    """Events cut to the window; those wholly outside are dropped."""
    lo, hi = window
    out = []
    for name, start, dur, detail in events:
        s, e = max(start, lo), min(start + dur, hi)
        if e > s:
            out.append((name, s, e - s, detail))
    return out


def merged_intervals(events):
    """Union of the events' intervals as a sorted list of [start, end]."""
    spans = sorted((s, s + d) for _, s, d, _ in events)
    merged = []
    for s, e in spans:
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return merged


def busy_seconds(events, window):
    """Seconds of the window in which at least one device op ran."""
    return sum(e - s for s, e in merged_intervals(clip(events, window))) / 1e9


def pattern_seconds(events, pattern, window=None):
    """Total device seconds (and count) of events whose name or detail
    matches ``pattern`` — a kernel's time. Overlap is not merged: one op
    runs at a time on a TPU core's XLA Ops line."""
    rx = re.compile(pattern)
    if window is not None:
        events = clip(events, window)
    hit = [d for name, _, d, detail in events
           if rx.search(name) or (detail and rx.search(detail))]
    return sum(hit) / 1e9, len(hit)


_LAYOUT = re.compile(r"\{[^{}]*\}")
_HLO = re.compile(r"^%?(?P<name>[^\s=]+)\s*=\s*(?P<type>.+?)\s(?P<op>[\w\-]+)\(")


def op_label(name, operands=False, limit=120):
    """A short, stable label for a device event. On a TPU the event's name
    is the whole HLO instruction (``%fusion.12 = bf16[8,128]{1,0:T(8,128)}
    fusion(...)``): keep the instruction's name with its instance number
    folded, its opcode and its result type without layouts — and, with
    ``operands``, the operand types too (a Mosaic kernel has no name of
    its own today and is told apart by its signature)."""
    text = _LAYOUT.sub("", name)
    m = _HLO.match(text)
    if not m:
        return (re.sub(r"[.\d]+$", "", name) or name)[:limit]
    label = "%s:%s %s" % (re.sub(r"[.\d]+$", "", m["name"]) or m["name"],
                          m["op"], m["type"])
    if operands:
        args = text[m.end():].split("), ")[0].rstrip(")")
        label += " <- " + re.sub(r"\s*%[\w.\-]+", "", args)
    return label[:limit]


def top_ops(events, window, n=10, operands=False, limit=120, only=None):
    """[[label, seconds, count]] of the device ops that took most time,
    summed by :func:`op_label`. ``only``: keep events whose name holds
    this string."""
    total = {}
    for name, _, dur, _ in clip(events, window):
        if only and only not in name:
            continue
        t = total.setdefault(op_label(name, operands, limit), [0, 0])
        t[0] += dur
        t[1] += 1
    ranked = sorted(total.items(), key=lambda kv: -kv[1][0])[:n]
    return [[k, v[0] / 1e9, v[1]] for k, v in ranked]


def idle_gaps(events, host_events, window, n=10):
    """[[label, seconds]]: the window's idle time, summed by what the host
    was doing at the middle of each gap — the innermost span of the
    program (``stf/<layer>/<phase>``, the whole name, cut at ``#``) open
    then on any thread; where none is, the innermost harness span
    (``chipbench:<label>``, the label alone); else ``unlabelled``. The
    ``n`` largest, or with ``n=None`` all."""
    lo, hi = window
    busy = merged_intervals(clip(events, window))
    gaps, cursor = [], lo
    for s, e in busy:
        if s > cursor:
            gaps.append((cursor, s))
        cursor = max(cursor, e)
    if hi > cursor:
        gaps.append((cursor, hi))
    spans = []  # (start, end, label, 0 for the program's / 1 the harness's)
    for name, s, d, _ in host_events:
        if name.startswith(PROGRAM_PREFIX):
            spans.append((s, s + d, name.split("#", 1)[0], 0))
        elif name.startswith(SPAN_PREFIX) and name != WINDOW_SPAN:
            spans.append((s, s + d, name[len(SPAN_PREFIX):], 1))
    spans.sort(key=lambda x: x[0])
    total, open_now, nxt = {}, [], 0
    for s, e in gaps:  # in time order, so spans open and close once
        mid = (s + e) // 2
        while nxt < len(spans) and spans[nxt][0] <= mid:
            open_now.append(spans[nxt])
            nxt += 1
        open_now = [sp for sp in open_now if sp[1] >= mid]
        # the program's before the harness's, then the narrowest, then the
        # one that opened first
        label = min(open_now, key=lambda sp: (sp[3], sp[1] - sp[0]),
                    default=(0, 0, "unlabelled"))[2]
        total[label] = total.get(label, 0) + (e - s)
    ranked = sorted(total.items(), key=lambda kv: -kv[1])[:n]
    return [[k, v / 1e9] for k, v in ranked]


def reduce(trace, chips=1):
    """The numbers every reader needs, computed once: the window, busy
    seconds averaged over the chips used, and the breakdown."""
    window = window_of(trace["host"])
    planes = sorted(trace["device"])[:chips]
    if not planes:
        raise ValueError("trace holds no /device:TPU plane with XLA Ops")
    busy = [busy_seconds(trace["device"][p], window) for p in planes]
    first = trace["device"][planes[0]]
    return {
        "window": window,
        "window_s": (window[1] - window[0]) / 1e9,
        "busy_s": sum(busy) / len(busy),
        "ops": first,
        "host": trace["host"],
        "lines": trace["lines"],
        "breakdown": {"device_ops": [op[:2] for op in
                                     top_ops(first, window)],
                      "idle_gaps": idle_gaps(first, trace["host"], window)},
    }
