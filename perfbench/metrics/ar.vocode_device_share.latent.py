"""Share of the device's busy time in the traced interval that vocoder
programs took (by program name: ``unit_vocode``; one per retired row)."""

from perfbench.harness import latent


def read(run):
    return latent.device_share(run, "vocode")
