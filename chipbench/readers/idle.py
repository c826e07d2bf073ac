"""Reader ``idle``: 1 - union of device-op intervals / traced window, in %."""


def read(params, facts):
    trace = facts.get("trace")
    if not trace or trace["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - trace["busy_s"] / trace["window_s"])
