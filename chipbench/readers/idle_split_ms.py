"""Reader ``idle_split_ms``: device-idle time split where the spans split it.

The window's idle gaps are those ``trace_reduce.idle_gaps`` finds (the
complement of the device ops' merged intervals, cut to the window). Where
that function gives a whole gap to the span open at its middle, this reader
CUTS each gap at every boundary of the program's ``stf/...`` spans and
gives each piece to the innermost span open over it — the narrowest, on any
host thread — or to ``unlabelled`` where none is. A piece under
``stf/session/await_device`` (the executor's explicit wait for the results
it fetches) is told apart by where its span opened:

- opened BEFORE the gap began: ``return`` — the device has ended and the
  host, which was waiting already, has yet to hear of it;
- opened INSIDE the gap: ``launch`` — the host waits for results of a
  program the device has not started.

**The two clocks are aligned first.** A profiler session places the
device's events on the host's clock only to within a millisecond or two,
and differently from session to session (PERF.md, PR 37: in one trace every
program began 1.4 ms before the runtime had enqueued it and ended 1.9 ms
before the runtime heard of it) — more than the pieces being told apart.
The TPU runtime writes its own host-side events into the same trace:
``DoEnqueueProgram`` when it hands a program to the chip and
``tpu::System::Execute=>Done`` when it hears that one has ended. Wherever the
host was synchronised with the device — at the end of an ``await_device``
span — the program waited for cannot have ended after the ``Done`` heard
inside that wait, and the next one cannot have begun before the first
enqueue after it: over all such places that leaves a window for the
device clock's lead, as wide as the smallest launch plus the smallest return
latency, and the device's events are moved by its MIDDLE (so what is left
of either latency is uncertain by half the window, which is logged). A
trace without those events, or whose window is empty, is left as it is.

params: ``per`` (the span that counts steps: whole spans inside the
window, as every span reader counts) and which pieces are summed — either
``take`` (a list of span names and/or the words ``return``, ``launch``) or
``all_but`` (the same kind of list: every piece under a program span but
these). The value is their idle time per step, in ms. Logged on an earlier
line, whatever is taken: seconds by innermost span after the split (with
``return`` and ``launch`` in place of the wait's own name), the idle time
under the program's spans per step, the share under no span, the count of
gaps, the median and the longest gap, ``clock_skew_window_ns`` and the
``clock_skew_ns`` applied, and ``min_return_ns`` — on the clocks AS
RECORDED, over the ``await_device`` spans that end in the window, the
smallest distance from the end of the device's busy interval that began
last before the span's end to that end: below zero the device's clock is
behind the host's, and far above the smallest round trip it is ahead.
Nothing to read — no ``per`` span whole inside the window, or no
``await_device`` span in the trace, as a program without the split leaves
it — returns ``None``, never 0.
"""

import bisect
import statistics

from chipbench import harness, trace_reduce
from chipbench.readers import _spans

AWAIT = _spans.PREFIX + "session/await_device"
UNLABELLED = "unlabelled"
# the TPU runtime's own host events: a program handed to the chip; the
# runtime hearing that one has ended
ENQUEUED = "DoEnqueueProgram"
COMPLETED = "tpu::System::Execute=>Done"
# device ops closer together than this were queued together: no round
# trip through the host fits between them (the smallest seen is 0.5 ms)
QUEUED_NS = 100_000
# a wait is matched to a burst of device work that ends this near its Done
MAX_SKEW_NS = 5_000_000


def clock_skew_window(busy, host, waits):
    """(lo, hi) in ns: by how much the device's clock is AHEAD of the
    host's in this trace, at least and at most, from the runtime's marks
    around every ``await_device`` span (``busy``: the device ops' merged
    intervals); None without them."""
    bursts = []
    for s, e in busy:
        if bursts and s - bursts[-1][1] < QUEUED_NS:
            bursts[-1][1] = e
        else:
            bursts.append([s, e])
    ends = [e for _, e in bursts]
    done = sorted(s for name, s, _, _ in host if name.startswith(COMPLETED))
    enqueued = sorted(s for name, s, _, _ in host
                      if name.startswith(ENQUEUED))
    if not ends or not done or not enqueued:
        return None
    lo, hi = [], []
    for _, opened, dur, _ in waits:
        closed = opened + dur
        heard = done[bisect.bisect_right(done, closed) - 1]
        if not opened <= heard <= closed:
            continue
        # the burst whose end lies nearest to where the runtime heard of it
        k = bisect.bisect_left(ends, heard)
        j = min((i for i in (k - 1, k) if 0 <= i < len(ends)),
                key=lambda i: abs(heard - ends[i]))
        if abs(heard - ends[j]) > MAX_SKEW_NS:
            continue
        hi.append(heard - ends[j])
        nxt = bisect.bisect_left(enqueued, closed)
        if j + 1 < len(bursts) and nxt < len(enqueued):
            lo.append(enqueued[nxt] - bursts[j + 1][0])
    if not lo or max(lo) > min(hi):
        return None
    return max(lo), min(hi)


def gaps_of(busy, window):
    """[(start, end)] of the window that no interval of ``busy`` covers."""
    lo, hi = window
    gaps, cursor = [], lo
    for s, e in busy:
        if e <= lo or s >= hi:
            continue
        if s > cursor:
            gaps.append((cursor, s))
        cursor = max(cursor, e)
    if hi > cursor:
        gaps.append((cursor, hi))
    return gaps


def split(gaps, spans):
    """{label: ns} of the gaps, each cut at every boundary of ``spans``
    (``[(name, start, dur, thread)]``) and each piece given to the
    narrowest span open over it; ``return`` / ``launch`` for a piece of
    the executor's wait."""
    spans = sorted((s, s + d, name) for name, s, d, _ in spans if d > 0)
    cuts = sorted({t for s, e, _ in spans for t in (s, e)})
    total, open_now, nxt = {}, [], 0
    for lo, hi in gaps:  # in time order, so spans open and close once
        inside = cuts[bisect.bisect_right(cuts, lo):bisect.bisect_left(cuts,
                                                                       hi)]
        for a, b in zip([lo, *inside], [*inside, hi]):
            while nxt < len(spans) and spans[nxt][0] <= a:
                open_now.append(spans[nxt])
                nxt += 1
            open_now = [sp for sp in open_now if sp[1] > a]
            start, _, label = min(open_now, key=lambda sp: sp[1] - sp[0],
                                  default=(0, 0, UNLABELLED))
            if label == AWAIT:
                label = "return" if start < lo else "launch"
            total[label] = total.get(label, 0) + (b - a)
    return total


def min_return_ns(busy, waits, window):
    """The smallest ``await_device`` end minus the end of the busy
    interval that began last before it; None where no wait ends in the
    window after a device op."""
    starts = [s for s, _ in busy]
    lo, hi = window
    found = []
    for _, s, d, _ in waits:
        end = s + d
        at = bisect.bisect_right(starts, end) - 1
        if lo <= end <= hi and at >= 0:
            found.append(end - busy[at][1])
    return min(found, default=None)


def read(params, facts):
    trace = facts.get("trace")
    if not trace:
        return None
    spans = _spans.program_spans(trace)
    window = trace["window"]
    steps = _spans.whole(spans, params["per"], window)
    if not steps or not spans.get(AWAIT):
        return None
    busy = trace_reduce.merged_intervals(trace["ops"])
    skew_window = clock_skew_window(busy, trace["host"], spans[AWAIT])
    skew = sum(skew_window) // 2 if skew_window else 0
    gaps = gaps_of([(s + skew, e + skew) for s, e in busy], window)
    pieces = split(gaps, [ev for evs in spans.values() for ev in evs])
    idle = sum(pieces.values())
    under = idle - pieces.get(UNLABELLED, 0)
    if "take" in params:
        taken = sum(pieces.get(label, 0) for label in params["take"])
    else:
        taken = under - sum(pieces.get(label, 0)
                            for label in params["all_but"])
    n = len(steps)
    lengths = [e - s for s, e in gaps]
    harness.log(idle_split={
        "per": params["per"], "steps": n,
        "taken": params.get("take") or {"all_but": params["all_but"]},
        "ms_per_step": taken / n / 1e6,
        "idle_s": idle / 1e9,
        "by_innermost_span_s": {k: v / 1e9 for k, v in sorted(
            pieces.items(), key=lambda kv: -kv[1])},
        "return_s": pieces.get("return", 0) / 1e9,
        "launch_s": pieces.get("launch", 0) / 1e9,
        "under_program_spans_ms_per_step": under / n / 1e6,
        "under_no_span_share": (idle - under) / idle if idle else None,
        "gaps": len(gaps),
        "gap_median_ns": statistics.median(lengths) if lengths else None,
        "gap_longest_ns": max(lengths, default=None),
        "clock_skew_window_ns": skew_window, "clock_skew_ns": skew,
        "min_return_ns": min_return_ns(busy, spans[AWAIT], window)})
    return taken / n / 1e6
