"""Reader ``kernel_ms``: a named kernel's device time per step, in ms.

params: ``pattern`` (regex over a device event's name and long name; the
kernels carry stable names, ``stf_<kernel>_<role>``, given to
``pl.pallas_call``), ``per`` (the program span that counts steps). Summed
device seconds of the matching events inside the traced window over the
``per`` spans lying wholly inside it. No matching event or no such span:
nothing returned — never 0.
"""

from chipbench import trace_reduce
from chipbench.readers import _spans


def read(params, facts):
    trace = facts.get("trace")
    if not trace:
        return None
    window = trace["window"]
    steps = _spans.whole(_spans.program_spans(trace), params["per"], window)
    secs, n = trace_reduce.pattern_seconds(trace["ops"], params["pattern"],
                                           window)
    if not steps or n == 0:
        return None
    return 1000.0 * secs / len(steps)
