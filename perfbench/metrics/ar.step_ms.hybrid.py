"""Device milliseconds of a step program (``nemotron_step``), the mean over
the traced interval."""

from perfbench.harness import hybrid


def read(run):
    took = hybrid.modules(run, "step")
    return 1e3 * sum(took) / len(took) if took else None
