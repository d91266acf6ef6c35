"""Share of the slots the window's step programs computed that held no row
(masked padding of the static shape): 1 - ``live_slot_steps`` over ``steps``
x ``slots`` of the step-group spans."""

from perfbench.harness import steps


def read(run):
    spans = steps.groups(run)
    computed = sum(g["steps"] * g["slots"] for g in spans)
    if not computed:
        return None
    return 100.0 * (1.0 - steps.total(spans, "live_slot_steps") / computed)
