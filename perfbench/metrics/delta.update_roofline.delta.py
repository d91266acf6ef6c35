"""The delta-rule update's share of its roofline: the least time the chip
could take to move the live rows' states in the traced interval's steps,
over the device time of the operations that touched a state
(``delta.state_seconds``: every operation of the interval whose text names
a float32 array of all the slots' states of one layer; the programs' reads
and writes of a state all stand under ``delta_op``).  The least is
``gigachat_costs.update_cost``'s: every live row's state once in and once
out (2 x 64 x 128 x 128 x 4 bytes a row and layer) at the chip's bandwidth,
seven operations an element, a layer a linear layer a step program in the
trace: the same work whatever implements it, so a kernel that reads the
state once is read by the same yardstick, and no implementation can read
over 100 %.  The time also holds what empty slots cost (the shape is
static) and a carried prompt's write of its slot.  From the means of the
step-group spans that ended inside the traced interval.  A program without
such a state: nothing."""

from perfbench.harness import costs, delta, gigachat_costs


def read(run):
    took = delta.modules(run, "step")
    mean = delta.mean_step(run, delta.traced_groups(run))
    touched = delta.state_seconds(run)
    if not took or mean is None or touched is None:
        return None
    bb = run["dims"]["backbone"]
    least = costs.roofline(gigachat_costs.update_cost(bb, mean[0]),
                           costs.peaks(run["device"]["kind"]))
    updates = len(took) * gigachat_costs.sizes(bb)["linear_layers"]
    return 100.0 * least["seconds"] * updates / touched[0]
