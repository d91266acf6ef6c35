"""Share of the device's busy time in the traced interval that prefill
programs took (by program name: ``sdar_prefill``; one per admitted row)."""

from perfbench.harness import blocks


def read(run):
    return blocks.device_share(run, "prefill")
