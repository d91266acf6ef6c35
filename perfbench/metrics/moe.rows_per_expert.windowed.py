"""Assignment rows a touched held expert gets in a step: the step-group
spans' ``held_assignments`` over ``held_experts_touched`` (the rows of a
group of the grouped matmul: 256 rows x 8 experts over 256 is 8; the
deployment's experts see 8 chips' rows, 64)."""

from perfbench.harness import windowed


def read(run):
    return windowed.ratio(run, "held_assignments", "held_experts_touched")
