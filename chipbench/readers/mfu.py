"""Reader ``mfu``: the whole step's share of the chip's peak, in %.

The runner counts, with chipbench/work.py, the FLOPs the mathematics needs
for everything processed inside the traced window
(``facts["work"]["model_flops"]``); this divides by the window and the peak.
"""


def read(params, facts):
    trace, work = facts.get("trace"), facts.get("work", {})
    flops = work.get("model_flops")
    if not trace or not flops or trace["window_s"] <= 0:
        return None
    peak = facts["peak"]["flops_per_s_bf16"] * facts["chips"]
    return 100.0 * flops / trace["window_s"] / peak
