"""Share of the device's busy time in the traced interval that vocoder
programs took (by program name: ``unit_vocode``; unit table, HiFi-GAN
generator and the int16 epilogue of one retired row)."""

from perfbench.harness import steps


def read(run):
    return steps.device_share(run, "vocode")
