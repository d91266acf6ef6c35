"""Share of the slots the window's pass programs computed that held no row
(masked padding of the static shape): 1 - ``live_slot_steps`` over ``steps``
x ``slots`` of the step-group spans."""

from perfbench.harness import blocks


def read(run):
    spans = blocks.groups(run)
    computed = sum(g["steps"] * g["slots"] for g in spans)
    if not computed:
        return None
    return 100.0 * (1.0 - blocks.total(spans, "live_slot_steps") / computed)
