"""Share of the device's busy time in the traced interval that prefill
programs took (by program name: ``laguna_prefill``; 0 where every prompt rode
a step)."""

from perfbench.harness import windowed


def read(run):
    return windowed.device_share(run, "prefill")
