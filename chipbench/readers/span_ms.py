"""Reader ``span_ms``: host time of the program's spans per step, in ms.

params: ``plus`` (span names summed), ``minus_inside`` (span names whose
part inside a plus span of the same thread is taken off: a layer's self
time is its span minus what its children cover), ``per`` (the span that
counts steps), ``log`` ({label: {"plus", "minus_inside"}}: further sums of
the same kind, logged per step on an earlier line and not reported). The
logged line also holds the mean of the whole ``per`` spans, their summed
time per step with the spans the window cuts counted by their inside part,
the traced stretch per step and the part of it that the ``per`` and plus
spans reach across (a span open when the profiler starts or stops is not
recorded, so the stretch's head and tail can lie under none), so that the
parts can be added up by hand. No ``per`` span whole
inside the window, or no plus span: nothing returned — never 0.
"""

from chipbench import harness, trace_reduce
from chipbench.readers import _spans


def read(params, facts):
    trace = facts.get("trace")
    if not trace:
        return None
    spans = _spans.program_spans(trace)
    window = trace["window"]
    steps = _spans.whole(spans, params["per"], window)
    if not steps or not any(spans.get(name) for name in params["plus"]):
        return None
    n = len(steps)
    value = 1000.0 * _spans.self_seconds(spans, params, window) / n
    named = trace_reduce.clip(
        [ev for name in [params["per"], *params["plus"]]
         for ev in spans.get(name, ())], window)
    spanned = (max(s + d for _, s, d, _ in named)
               - min(s for _, s, _, _ in named))
    harness.log(span_ms={
        "plus": params["plus"], "per": params["per"], "steps": n,
        "ms_per_step": value,
        "per_span_mean_ms": sum(ev[2] for ev in steps) / n / 1e6,
        "per_span_ms_per_step": 1000.0 * _spans.seconds(
            spans, [params["per"]], window) / n,
        "window_ms_per_step": 1000.0 * trace["window_s"] / n,
        "spanned_ms_per_step": spanned / n / 1e6,
        "also": {label: 1000.0 * _spans.self_seconds(spans, expr, window) / n
                 for label, expr in params.get("log", {}).items()}})
    return value
