"""Distinct experts a pass reads in an expert layer, the mean over the
window's passes and the layers: the step-group spans' ``experts_touched``
over ``steps`` x layers."""

from perfbench.harness import blocks


def read(run):
    spans = blocks.groups(run)
    n = sum(g["steps"] * len(g["layers"]) for g in spans)
    return blocks.total(spans, "experts_touched") / n if n else None
