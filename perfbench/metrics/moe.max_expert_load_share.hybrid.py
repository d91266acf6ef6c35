"""Share of a step's assignments that went to its fullest expert (of all
128 the router scores), over the window's steps and layers: the step-group
spans' ``max_expert_assignments`` over ``assignments`` (even routing reads
0.8 %)."""

from perfbench.harness import hybrid


def read(run):
    return hybrid.ratio(run, "max_expert_assignments", "assignments", 100.0)
