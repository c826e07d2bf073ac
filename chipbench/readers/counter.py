"""Reader ``counter``: a program counter or sampler over the window.

params: ``metric`` (the program's metric name), ``labels`` (list; the
string ``{model}`` stands for the served model's name), ``stat``
(``delta`` of a counter, or ``mean`` of a sampler's samples added in the
window), ``scale`` (multiplier, e.g. 100 for a share in %, 1000 for ms).
"""


def read(params, facts):
    key = params["metric"]
    pair = facts.get("counters", {}).get(key)
    if pair is None or pair[0] is None or pair[1] is None:
        return None
    before, after = pair
    scale = params.get("scale", 1.0)
    if params.get("stat", "delta") == "mean":
        n = after["count"] - before["count"]
        if n <= 0:
            return None
        return (after["sum"] - before["sum"]) / n * scale
    return (after - before) * scale
