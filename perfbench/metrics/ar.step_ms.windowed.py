"""Device milliseconds of a step program (``laguna_step``, and
``laguna_step_admit`` where a launch carried an arrival), the mean over the
traced interval."""

from perfbench.harness import windowed


def read(run):
    took = windowed.modules(run, "step")
    return 1e3 * sum(took) / len(took) if took else None
