"""Share of the device's busy time in the traced interval that prefill
programs took (by program name: ``pangu_prefill``; 0 where every prompt rode
a step)."""

from perfbench.harness import latent


def read(run):
    return latent.device_share(run, "prefill")
