"""Percent of the live rows' assignments that fell on experts the chip
holds: the step-group spans' ``held_assignments`` over ``assignments`` (32 of
256 experts held: an even router reads 12.5; the others' products are the
other 7 chips')."""

from perfbench.harness import windowed


def read(run):
    return windowed.ratio(run, "held_assignments", "assignments", 100.0)
