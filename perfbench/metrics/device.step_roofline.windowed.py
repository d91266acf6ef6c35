"""The step programs' share of their roofline: the least time the chip could
take for the steps of the traced interval, ``max(bytes / bandwidth,
operations / peak)``, over their device time.  Operations and bytes are
counted by ``perfbench/harness/laguna_costs.py`` from the configuration's
sizes and from what the step-group spans that ended inside the traced
interval (``shapes.traced_interval``) say the steps did: the held experts
touched, every other weight once, the keys and values of the places the
live rows read as held (every position in a full layer, at most the window
in a ring: the spans' ``kv_cache_bytes``), logits written.  The means
over those spans are applied to every step program in the trace.  At the
cell's shapes the bound is bytes, with the operations over half of it."""

from perfbench.harness import costs, windowed


def read(run):
    took = windowed.modules(run, "step")
    cost = windowed.mean_step_cost(run, windowed.traced_groups(run))
    if not took or cost is None:
        return None
    least = costs.roofline(cost, costs.peaks(run["device"]["kind"]))
    return 100.0 * least["seconds"] * len(took) / sum(took)
