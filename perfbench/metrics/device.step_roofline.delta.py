"""The step programs' share of their roofline: the least time the chip could
take for the steps of the traced interval, ``max(bytes / bandwidth,
operations / peak)``, over their device time.  Operations and bytes are
counted by ``perfbench/harness/gigachat_costs.py`` from the configuration's
sizes and from what the step-group spans that ended inside the traced
interval (``shapes.traced_interval``) say the steps did: the held experts
touched, every other weight once, every live row's delta-rule state and
convolution columns once in and once out, the latent rows the live rows
attended over (576 values a position, once), logits written.  The means
over those spans are applied to every step program in the trace.  At the
cell's shapes the bound is bytes, three fifths of them state."""

from perfbench.harness import costs, delta


def read(run):
    took = delta.modules(run, "step")
    cost = delta.mean_step_cost(run, delta.traced_groups(run))
    if not took or cost is None:
        return None
    least = costs.roofline(cost, costs.peaks(run["device"]["kind"]))
    return 100.0 * least["seconds"] * len(took) / sum(took)
