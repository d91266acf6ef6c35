"""Percent of the window's steps whose program ran the expert products by
this repo's grouped matmul: the step-group spans' ``steps`` where their
``expert_matmul`` says ``grouped``, over all their ``steps``.  A program
whose spans do not say what their products ran (before the attribute):
nothing to read."""

from perfbench.harness import steps


def read(run):
    spans = [g for g in steps.groups(run) if "expert_matmul" in g]
    n = steps.total(spans, "steps")
    grouped = [g for g in spans if g["expert_matmul"] == "grouped"]
    return 100.0 * steps.total(grouped, "steps") / n if n else None
