"""Live rows per pass program: the step-group spans' ``live_slot_steps``
over their ``steps`` (the window's spans: what ended inside it)."""

from perfbench.harness import blocks


def read(run):
    return blocks.ratio(run, "live_slot_steps", "steps")
