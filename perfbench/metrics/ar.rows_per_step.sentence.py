"""Live rows per step: the step-group spans' ``live_slot_steps`` over their
``steps`` (the window's spans: what ended inside it)."""

from perfbench.harness import steps


def read(run):
    spans = steps.groups(run)
    n = steps.total(spans, "steps")
    return steps.total(spans, "live_slot_steps") / n if n else None
