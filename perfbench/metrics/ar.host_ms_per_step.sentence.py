"""Host milliseconds of the step loop per step, none of them blocked on the
device: the step-group spans' ``host_ms`` (launch, admit, retire) over
their ``steps``.  The loop keeps one step queued behind the running one, so
this shows on the device only where it exceeds a step's device time."""

from perfbench.harness import steps


def read(run):
    spans = steps.groups(run)
    n = steps.total(spans, "steps")
    if not n:
        return None
    return sum(sum(g["host_ms"].values()) for g in spans) / n
