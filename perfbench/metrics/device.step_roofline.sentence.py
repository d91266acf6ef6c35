"""The step programs' share of their roofline: the least time the chip
could take for the steps of the traced interval, over their device time.
Operations and bytes are counted by ``perfbench/harness/lfm2_costs.py`` from
the configuration's sizes and from what the step-group spans that ended
inside the traced interval (``shapes.traced_interval``) say the steps did:
the experts touched (bytes = their held weights, every other weight once,
keys and values read), the live rows (operations).  The means over those
spans are applied to every step program in the trace.  At the cell's shapes
the bound is bytes (``roofline()["bound"]``)."""

from perfbench.harness import costs, lfm2_costs, steps


def read(run):
    took = steps.modules(run, "step")
    spans = steps.traced_groups(run)
    n = steps.total(spans, "steps")
    if not took or not n:
        return None
    cost = lfm2_costs.step_cost(
        run["dims"]["backbone"],
        steps.total(spans, "live_slot_steps") / n,
        steps.total(spans, "experts_touched") / n,
        steps.total(spans, "kv_positions") / n)
    least = costs.roofline(cost, costs.peaks(run["device"]["kind"]))
    return 100.0 * least["seconds"] * len(took) / sum(took)
