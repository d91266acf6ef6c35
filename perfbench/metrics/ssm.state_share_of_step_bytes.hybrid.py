"""Percent of the bytes a step needs that are recurrent state read and
written: the step-group spans' ``ssm_state_bytes`` over their ``steps``,
over the bytes ``perfbench/harness/nemotron_costs.py`` counts for the
window's mean step (touched held experts, every other weight, state, keys
and values, logits).  A program whose spans state no such bytes: nothing."""

from perfbench.harness import hybrid


def read(run):
    spans = [g for g in hybrid.groups(run) if "ssm_state_bytes" in g]
    cost = hybrid.mean_step_cost(run, spans)
    if cost is None:
        return None
    state = hybrid.total(spans, "ssm_state_bytes") / hybrid.total(spans,
                                                                  "steps")
    return 100.0 * state / cost["bytes"]
