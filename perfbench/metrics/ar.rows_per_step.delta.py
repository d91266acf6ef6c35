"""Live rows per step, read as ``ar.rows_per_step.sentence`` reads them: the
step-group spans' ``live_slot_steps`` over their ``steps`` (of 256 slots)."""

from perfbench.harness import delta

read = delta.sibling("ar.rows_per_step.sentence")
