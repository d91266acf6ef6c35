"""Percent of the bytes a step needs that are keys and values read: the
step-group spans' ``kv_cache_bytes`` (the places the live rows held: every
position in a full layer, at most the window in a ring, 4096 bytes each)
over their ``steps``, over the bytes ``perfbench/harness/laguna_costs.py``
counts for the window's mean step (touched held experts, every other
weight, those places, logits).  A program whose spans state no such bytes:
nothing."""

from perfbench.harness import windowed


def read(run):
    spans = windowed.windowed(windowed.groups(run))
    cost = windowed.mean_step_cost(run, spans)
    if cost is None:
        return None
    held = windowed.total(spans, "kv_cache_bytes") / windowed.total(spans,
                                                                    "steps")
    return 100.0 * held / cost["bytes"]
