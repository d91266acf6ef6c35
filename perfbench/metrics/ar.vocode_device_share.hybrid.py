"""Share of the device's busy time in the traced interval that vocoder
programs took (by program name: ``unit_vocode``; one per retired row)."""

from perfbench.harness import hybrid


def read(run):
    return hybrid.device_share(run, "vocode")
