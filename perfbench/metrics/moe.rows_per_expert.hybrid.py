"""Assignment rows a touched held expert gets in a step: the step-group
spans' ``held_assignments`` over ``held_experts_touched`` (the rows of a
group of the grouped matmul: 256 rows x 6 experts over 128 is 12; the
deployment's experts see two chips' rows, 24)."""

from perfbench.harness import hybrid


def read(run):
    return hybrid.ratio(run, "held_assignments", "held_experts_touched")
