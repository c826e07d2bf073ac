"""What the span readers share: the program's own spans in a trace.

The program's one span primitive (``platform/monitoring.traceme``) writes
``stf/<layer>/<phase>`` annotations into the profiler's trace, on every
host thread. A span's name is the text before any ``#`` (metadata may
arrive as a ``#k=v#`` suffix or as event stats). "Per step" is always per
span of the ``per`` name that lies wholly inside the traced window; sums
are over spans cut to the window. A trace of a program without such spans
yields empty results, and every reader then returns nothing.
"""

from chipbench import trace_reduce

PREFIX = trace_reduce.PROGRAM_PREFIX


def program_spans(trace):
    """{name: [(name, start_ns, dur_ns, thread)]} of the ``stf/...`` host
    events, names cut at ``#``, not yet cut to the window."""
    out = {}
    for name, start, dur, thread in trace["host"]:
        if name.startswith(PREFIX):
            name = name.split("#", 1)[0]
            out.setdefault(name, []).append((name, start, dur, thread))
    return out


def whole(spans, name, window):
    """The spans of ``name`` that lie wholly inside the window."""
    lo, hi = window
    return [ev for ev in spans.get(name, ())
            if ev[1] >= lo and ev[1] + ev[2] <= hi]


def seconds(spans, names, window):
    """Summed seconds of the named spans, each cut to the window."""
    return sum(d for name in names
               for _, _, d, _ in trace_reduce.clip(spans.get(name, ()),
                                                   window)) / 1e9


def seconds_inside(spans, inner, outer, window):
    """Seconds of the ``inner`` spans that lie inside an ``outer`` span of
    the same thread, all cut to the window."""
    total = 0
    for thread in {ev[3] for name in outer for ev in spans.get(name, ())}:
        cover = trace_reduce.merged_intervals(trace_reduce.clip(
            [ev for name in outer for ev in spans.get(name, ())
             if ev[3] == thread], window))
        for name in inner:
            for _, s, d, th in trace_reduce.clip(spans.get(name, ()),
                                                 window):
                if th == thread:
                    total += sum(max(0, min(s + d, hi) - max(s, lo))
                                 for lo, hi in cover)
    return total / 1e9


def self_seconds(spans, expr, window):
    """``expr`` = {"plus": [names], "minus_inside": [names]}: the plus
    spans' seconds without the part the minus spans cover inside them."""
    plus = expr["plus"]
    return (seconds(spans, plus, window)
            - seconds_inside(spans, expr.get("minus_inside", ()), plus,
                             window))
