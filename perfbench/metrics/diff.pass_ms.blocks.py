"""Device milliseconds of a pass program (``sdar_pass``), the mean over
the traced interval."""

from perfbench.harness import blocks


def read(run):
    took = blocks.modules(run, "step")
    return 1e3 * sum(took) / len(took) if took else None
