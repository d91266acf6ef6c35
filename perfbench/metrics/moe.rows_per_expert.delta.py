"""Assignment rows a touched held expert gets in a step: the step-group
spans' ``held_assignments`` over ``held_experts_touched`` (the rows of a
group of the grouped matmul: 256 rows x 8 experts over 256 is 8; the
deployment's experts see 32 chips' rows, 256)."""

from perfbench.harness import delta


def read(run):
    return delta.ratio(run, "held_assignments", "held_experts_touched")
