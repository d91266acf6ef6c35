"""Share of the device's busy time in the traced interval that vocoder
programs took (by program name: ``unit_vocode``; one per retired row)."""

from perfbench.harness import delta


def read(run):
    return delta.device_share(run, "vocode")
