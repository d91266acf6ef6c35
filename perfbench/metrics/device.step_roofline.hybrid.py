"""The step programs' share of their roofline: the least time the chip could
take for the steps of the traced interval, over their device time.
Operations and bytes are counted by ``perfbench/harness/nemotron_costs.py``
from the configuration's sizes and from what the step-group spans that ended
inside the traced interval (``shapes.traced_interval``) say the steps did:
the held experts touched (their weights at the published 1856 columns),
every other weight once, the live rows' recurrent state read and written,
keys and values read, logits written.  The means over those spans are
applied to every step program in the trace.  At the cell's shapes the bound
is bytes.  The state update is XLA's own (no kernel of this repo), so this
is the bound it is held to."""

from perfbench.harness import costs, hybrid


def read(run):
    took = hybrid.modules(run, "step")
    cost = hybrid.mean_step_cost(run, hybrid.traced_groups(run))
    if not took or cost is None:
        return None
    least = costs.roofline(cost, costs.peaks(run["device"]["kind"]))
    return 100.0 * least["seconds"] * len(took) / sum(took)
