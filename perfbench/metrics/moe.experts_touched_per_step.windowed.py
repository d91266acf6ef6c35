"""Distinct experts **of those the chip holds** (32 a layer) that a step
reads in an expert layer, the mean over the window's steps and the layers:
the step-group spans' ``held_experts_touched`` over ``steps`` x layers."""

from perfbench.harness import windowed


def read(run):
    spans = [g for g in windowed.groups(run) if "held_experts_touched" in g]
    n = sum(g["steps"] * len(g["layers"]) for g in spans)
    return windowed.total(spans, "held_experts_touched") / n if n else None
