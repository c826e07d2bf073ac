"""Reader ``trace_events``: a kernel's share of its roofline, in %.

params: ``pattern`` (regex over a device op's name and long name, written
from a real trace), ``work`` (key under ``facts["work"]`` holding the
``[flops, bytes]`` the runner counted, with chipbench/work.py, for every
call of that kernel inside the traced window). The least time the chip
could take, max(flops/peak, bytes/bandwidth), over the summed device
durations of the matching events. Nothing matching: nothing returned —
never 0.
"""

from chipbench import trace_reduce, work


def read(params, facts):
    trace = facts.get("trace")
    counted = facts.get("work", {}).get(params["work"])
    if not trace or not counted:
        return None
    seconds, n = trace_reduce.pattern_seconds(
        trace["ops"], params["pattern"], trace["window"])
    if n == 0 or seconds <= 0:
        return None
    least, _ = work.roofline_seconds(counted[0], counted[1], facts["peak"])
    return 100.0 * least / seconds
