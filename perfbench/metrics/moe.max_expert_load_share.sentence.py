"""Share of a step's assignments that went to its fullest expert, over
the window's steps and expert layers: the step-group spans'
``max_expert_assignments`` over ``assignments`` (even routing over 64
experts reads 1.6 %; the fullest expert's rows set the grouped matmul's
longest group)."""

from perfbench.harness import steps


def read(run):
    spans = steps.groups(run)
    n = steps.total(spans, "assignments")
    if not n:
        return None
    return 100.0 * steps.total(spans, "max_expert_assignments") / n
