"""Host milliseconds of the step loop per pass program, none of them
blocked on the device: the step-group spans' ``host_ms`` (launch, admit,
retire) over their ``steps``.  The loop keeps one launch queued behind the
running one, so this shows on the device only where it exceeds a pass's
device time."""

from perfbench.harness import blocks


def read(run):
    spans = blocks.groups(run)
    n = blocks.total(spans, "steps")
    if not n:
        return None
    return sum(sum(g["host_ms"].values()) for g in spans) / n
