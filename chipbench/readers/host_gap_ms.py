"""Reader ``host_gap_ms``: device-idle time under the program's spans.

params: ``per`` (the program span that counts steps), ``scope`` (regex: a
device event that matches carries the name of the stf op that made it).
The window's idle gaps, found and labelled by ``trace_reduce.idle_gaps``
(the innermost ``stf/...`` span open on any host thread at the gap's
middle, else a harness span, else ``unlabelled``); the value is the idle
time under a span of the PROGRAM, per step, in ms. Logged on an earlier
line: idle seconds by label, the share that lies under no program span,
and the share of device-busy time whose event matches ``scope``. A trace
without program spans: nothing returned.
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
    gaps = dict(trace_reduce.idle_gaps(trace["ops"], trace["host"], window,
                                       n=None))
    idle = sum(gaps.values())
    under_none = sum(v for label, v in gaps.items()
                     if not label.startswith(_spans.PREFIX))
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
