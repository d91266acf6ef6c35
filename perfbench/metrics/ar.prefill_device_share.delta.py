"""Share of the device's busy time in the traced interval that prefill
programs took (by program name: ``gigachat_prefill``; 0 where every prompt
rode a step)."""

from perfbench.harness import delta


def read(run):
    return delta.device_share(run, "prefill")
