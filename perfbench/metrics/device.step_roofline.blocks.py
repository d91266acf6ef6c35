"""The pass programs' share of their roofline: the least time the chip
could take for the passes of the traced interval, over their device time.
Operations and bytes are counted by ``perfbench/harness/sdar_costs.py`` from
the configuration's sizes and from what the step-group spans that ended
inside the traced interval (``shapes.traced_interval``) say the passes did:
the experts touched (bytes = their weights, every other weight once, keys
and values read, logits written), the live rows (operations).  The means
over those spans are applied to every pass program in the trace.  At the
cell's shapes the bound is bytes."""

from perfbench.harness import blocks, costs, sdar_costs


def read(run):
    took = blocks.modules(run, "step")
    spans = blocks.traced_groups(run)
    n = blocks.total(spans, "steps")
    if not took or not n:
        return None
    cost = sdar_costs.pass_cost(
        run["dims"]["backbone"], int(run["dims"]["units"]["block_length"]),
        blocks.total(spans, "live_slot_steps") / n,
        blocks.total(spans, "experts_touched") / n,
        blocks.total(spans, "kv_positions") / n)
    least = costs.roofline(cost, costs.peaks(run["device"]["kind"]))
    return 100.0 * least["seconds"] * len(took) / sum(took)
