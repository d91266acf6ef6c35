"""Share of the traced interval in which no operation ran on the device."""


def read(run):
    trace = run.get("trace") or {}
    if not trace.get("busy_s"):
        return None
    return 100.0 * (1.0 - trace["busy_s"] / trace["window_s"])
