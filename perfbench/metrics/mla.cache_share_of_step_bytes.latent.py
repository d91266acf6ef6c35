"""Percent of the bytes a step needs that are latent rows read: the
step-group spans' ``latent_cache_bytes`` (as stored: 640 lanes a row) over
their ``steps``, over the bytes ``perfbench/harness/pangu_costs.py`` counts
for the window's mean step (touched held experts, every other weight, the
rows at their 576 values, logits).  A program whose spans state no such
bytes: nothing."""

from perfbench.harness import latent


def read(run):
    spans = [g for g in latent.groups(run) if g.get("latent_cache_bytes")]
    cost = latent.mean_step_cost(run, spans)
    if cost is None:
        return None
    rows = latent.total(spans, "latent_cache_bytes") / latent.total(spans,
                                                                    "steps")
    return 100.0 * rows / cost["bytes"]
