"""Share of the device's busy time in the traced interval that prefill
programs took (by program name: ``nemotron_prefill``; one per admitted
row)."""

from perfbench.harness import hybrid


def read(run):
    return hybrid.device_share(run, "prefill")
