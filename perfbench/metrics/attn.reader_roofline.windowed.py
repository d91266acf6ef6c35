"""The cache reader's share of its roofline: the least time the chip could
take for the reads of both kinds of cache in the traced interval's steps,
over the device time of the kernel that made them (``slot_attention``, by
its operations' names: ``windowed.kernel_seconds``; a full layer's read and
a ring's are operations of the same name).  Operations and bytes of a
step's reads are ``laguna_costs.attention_cost``'s: the places the live
rows *held* (every position in a full layer, at most the window in a ring:
the step groups' ``kv_cache_bytes``), keys and values once, not the tiles of
256 places they were fetched in; the queries in and the results out; per
place and query head a product for the score and one for the value;
whichever of bytes and operations bounds.  A count that is a floor, so the
share cannot pass 100 %.  From the means of the step-group spans that ended
inside the traced interval, applied to every step program in the trace.
Where the einsum reads the caches (no kernel, no name), or the spans state
no ``kv_cache_bytes``: nothing."""

from perfbench.harness import costs, laguna_costs, windowed


def read(run):
    took = windowed.modules(run, "step")
    mean = windowed.mean_step(run, windowed.traced_groups(run))
    if not took or mean is None:
        return None
    kernel = windowed.kernel_seconds(run)
    if not kernel:
        return None
    least = costs.roofline(
        laguna_costs.attention_cost(run["dims"]["backbone"], mean[0],
                                    mean[3], mean[4]),
        costs.peaks(run["device"]["kind"]))
    return 100.0 * least["seconds"] * len(took) / kernel
