"""Share of a step's assignments that went to its fullest expert (of all
256 the router scores), over the window's steps and layers: the step-group
spans' ``max_expert_assignments`` over ``assignments`` (even routing reads
0.4 %)."""

from perfbench.harness import delta


def read(run):
    return delta.ratio(run, "max_expert_assignments", "assignments", 100.0)
