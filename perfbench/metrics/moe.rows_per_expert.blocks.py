"""Assignment rows a touched expert gets in a pass: the step-group spans'
``assignments`` over ``experts_touched`` (the rows of a group of the
grouped matmul: 64 x 4 positions x 8 experts over 128 is 16)."""

from perfbench.harness import blocks


def read(run):
    return blocks.ratio(run, "assignments", "experts_touched")
