"""Device milliseconds of a step program (``lfm2_step``), the mean over
the traced interval."""

from perfbench.harness import steps


def read(run):
    took = steps.modules(run, "step")
    return 1e3 * sum(took) / len(took) if took else None
