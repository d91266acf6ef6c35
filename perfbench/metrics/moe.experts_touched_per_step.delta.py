"""Distinct experts **of those the chip holds** (8 a layer) that a step
reads in an expert layer, the mean over the window's steps and the layers:
the step-group spans' ``held_experts_touched`` over ``steps`` x layers."""

from perfbench.harness import delta


def read(run):
    spans = [g for g in delta.groups(run) if "held_experts_touched" in g]
    n = sum(g["steps"] * len(g["layers"]) for g in spans)
    return delta.total(spans, "held_experts_touched") / n if n else None
