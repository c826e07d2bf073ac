"""Reader ``host_gap_ms``: device-idle time under the program's spans.

params: ``per`` (the program span that counts steps), ``scope`` (regex: a
device event that matches carries the name of the stf op that made it).
The window's idle gaps are found as ``trace_reduce.idle_gaps`` finds them
and labelled by the innermost ``stf/...`` span open on any host thread at
the gap's middle; the value is the idle time that has such a label, per
step, in ms. Logged on an earlier line: idle seconds by innermost span,
what lies under none (``unlabelled``) and its share, and the share of
device-busy time whose event matches ``scope``. A trace without program
spans: nothing returned.
"""

import re

from chipbench import harness, trace_reduce
from chipbench.readers import _spans


def read(params, facts):
    trace = facts.get("trace")
    if not trace:
        return None
    spans = _spans.program_spans(trace)
    window = trace["window"]
    steps = _spans.whole(spans, params["per"], window)
    if not steps:
        return None
    # idle_gaps labels by the harness's own prefix: hand it the program's
    # spans under that prefix, whole names kept as the labels
    as_harness = [(trace_reduce.SPAN_PREFIX + name, s, d, th)
                  for evs in spans.values() for name, s, d, th in evs]
    gaps = dict(trace_reduce.idle_gaps(trace["ops"], as_harness, window,
                                       n=len(spans) + 1))
    idle = sum(gaps.values())
    under_none = gaps.get("unlabelled", 0.0)
    scope = re.compile(params["scope"])
    busy = scoped = 0
    for name, _, dur, detail in trace_reduce.clip(trace["ops"], window):
        busy += dur
        if scope.search(name) or (detail and scope.search(detail)):
            scoped += dur
    harness.log(host_gap={
        "per": params["per"], "steps": len(steps), "idle_s": idle,
        "by_innermost_span_s": gaps,
        "under_no_span_share": under_none / idle if idle else None,
        "busy_with_stf_scope_share": scoped / busy if busy else None})
    return 1000.0 * (idle - under_none) / len(steps)
