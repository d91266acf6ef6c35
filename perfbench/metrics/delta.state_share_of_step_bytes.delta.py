"""Percent of the bytes a step needs that are delta-rule state and
convolution columns read and written: the step-group spans'
``delta_state_bytes`` over their ``steps``, over the bytes
``perfbench/harness/gigachat_costs.py`` counts for the window's mean step
(touched held experts, every other weight, state, latent rows, logits).  A
program whose spans state no such bytes: nothing."""

from perfbench.harness import delta


def read(run):
    spans = [g for g in delta.groups(run) if g.get("delta_state_bytes")]
    cost = delta.mean_step_cost(run, spans)
    if cost is None:
        return None
    state = delta.total(spans, "delta_state_bytes") / delta.total(spans,
                                                                  "steps")
    return 100.0 * state / cost["bytes"]
