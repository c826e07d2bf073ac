"""Reader ``span_minus_busy``: host time per call the device did not cover.

params: ``span`` (the harness span's label). Over the spans that lie wholly
inside the traced window: their mean wall time times the window's idle
share — wall per call minus device-busy per call — in ms.
"""


def read(params, facts):
    trace = facts.get("trace")
    spans = facts.get("spans_in_trace", {}).get(params["span"])
    if not trace or not spans or trace["window_s"] <= 0:
        return None
    mean_wall = sum(b - a for a, b in spans) / len(spans)
    return 1000.0 * mean_wall * (1.0 - trace["busy_s"] / trace["window_s"])
