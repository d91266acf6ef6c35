"""Share of a pass's assignments that went to its fullest expert, over the
window's passes and layers: the step-group spans' ``max_expert_assignments``
over ``assignments`` (even routing over 128 experts reads 0.8 %; the
fullest expert's rows set the grouped matmul's longest group)."""

from perfbench.harness import blocks


def read(run):
    return blocks.ratio(run, "max_expert_assignments", "assignments", 100.0)
