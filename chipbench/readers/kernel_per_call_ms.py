"""Reader ``kernel_per_call_ms``: a kernel's device time per execution of
the device program it runs in, in ms, counted on the device.

``kernel_ms`` counts steps by a host span, which fits a program the host
waits on. A prefill the engine enqueues all at once (a first fill's ~90
calls) runs for seconds after its spans closed, so no host span counts it.
params: ``pattern`` (regex over a device event's name, as ``kernel_ms``),
``per_call`` (regex of the one device event every execution of the program
has once). Summed device seconds of the ``pattern`` events over the count
of ``per_call`` events, both inside the traced window. No matching event
of either: nothing returned — never 0.
"""

from chipbench import trace_reduce


def read(params, facts):
    trace = facts.get("trace")
    if not trace:
        return None
    window = trace["window"]
    secs, n = trace_reduce.pattern_seconds(trace["ops"], params["pattern"],
                                           window)
    _, calls = trace_reduce.pattern_seconds(trace["ops"], params["per_call"],
                                            window)
    if n == 0 or calls == 0:
        return None
    return 1000.0 * secs / calls
