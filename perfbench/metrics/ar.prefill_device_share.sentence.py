"""Share of the device's busy time in the traced interval that prefill
programs took (by program name: ``lfm2_prefill``; one per admitted row)."""

from perfbench.harness import steps


def read(run):
    return steps.device_share(run, "prefill")
