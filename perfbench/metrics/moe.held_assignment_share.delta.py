"""Percent of the live rows' assignments that fell on experts the chip
holds: the step-group spans' ``held_assignments`` over ``assignments`` (8 of
256 experts held: an even router reads 3.1; the others' products are the
other 31 chips')."""

from perfbench.harness import delta


def read(run):
    return delta.ratio(run, "held_assignments", "assignments", 100.0)
