"""Percent of the live rows' assignments that fell on experts the chip
holds: the step-group spans' ``held_assignments`` over ``assignments`` (64 of
128 experts held: an even router reads 50; the others' products are the
other chip's)."""

from perfbench.harness import hybrid


def read(run):
    return hybrid.ratio(run, "held_assignments", "assignments", 100.0)
