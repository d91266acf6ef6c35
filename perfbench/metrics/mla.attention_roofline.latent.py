"""The latent reader's share of its roofline: the least time the chip could
take for the reads of the latent cache in the traced interval's steps, over
the device time of the kernel that made them (``latent_attention``, by its
operations' names: ``latent.kernel_seconds``).  Operations and bytes of a
read are ``pangu_costs.attention_cost``'s (the rows the live rows attended
over, once, at 576 values; the queries in and the results out; per position
and head a product over the row and one over its 512 values), from the
means of the step-group spans that ended inside the traced interval, a
read a layer a step program in the trace.  Near the chip's ridge: whichever
of bytes and operations bounds it says the mean row's length.  Where the
einsum reads the cache (no kernel, no name): nothing."""

from perfbench.harness import costs, latent, pangu_costs


def read(run):
    took = latent.modules(run, "step")
    mean = latent.mean_step(run, latent.traced_groups(run))
    if not took or mean is None:
        return None
    kernel = latent.kernel_seconds(run)
    if not kernel:
        return None
    bb = run["dims"]["backbone"]
    least = costs.roofline(
        pangu_costs.attention_cost(bb, mean[0], mean[3]),
        costs.peaks(run["device"]["kind"]))
    reads = len(took) * int(bb["num_hidden_layers"])
    return 100.0 * least["seconds"] * reads / kernel
