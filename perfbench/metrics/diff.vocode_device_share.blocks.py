"""Share of the device's busy time in the traced interval that vocoder
programs took (by program name: ``unit_vocode``; one per retired row)."""

from perfbench.harness import blocks


def read(run):
    return blocks.device_share(run, "vocode")
