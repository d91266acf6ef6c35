"""Share of the device's busy time in the traced interval that vocoder
programs took (by program name: ``unit_vocode``; one per retired row)."""

from perfbench.harness import windowed


def read(run):
    return windowed.device_share(run, "vocode")
