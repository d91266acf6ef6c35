"""Distinct experts a step reads in an expert layer, the mean over the
window's steps and the expert layers: the step-group spans'
``experts_touched`` over ``steps`` x layers."""

from perfbench.harness import steps


def read(run):
    spans = steps.groups(run)
    n = sum(g["steps"] * len(g["layers"]) for g in spans)
    return steps.total(spans, "experts_touched") / n if n else None
