"""The latent reader's share of its roofline in this cell, read as
``mla.attention_roofline.latent`` reads it: the least time the chip could
take for the reads of the latent cache in the traced interval's steps
(``pangu_costs.attention_cost`` at this geometry: 64 heads, the rows the
live rows attended over, once, at 576 values), over the device time of the
kernel that made them (``latent_attention``, by its operations' names), a
read a full layer (one) a step program in the trace.  Where the einsum
reads the cache (no kernel, no name): nothing."""

from perfbench.harness import costs, delta, gigachat_costs


def read(run):
    took = delta.modules(run, "step")
    mean = delta.mean_step(run, delta.traced_groups(run))
    if not took or mean is None:
        return None
    kernel = delta.kernel_seconds(run)
    if not kernel:
        return None
    bb = run["dims"]["backbone"]
    least = costs.roofline(
        gigachat_costs.attention_cost(bb, mean[0], mean[3]),
        costs.peaks(run["device"]["kind"]))
    reads = len(took) * gigachat_costs.sizes(bb)["full_layers"]
    return 100.0 * least["seconds"] * reads / kernel
