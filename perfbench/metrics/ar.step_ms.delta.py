"""Device milliseconds of a step program (``gigachat_step``, and
``gigachat_step_admit`` where a launch carried an arrival), the mean over
the traced interval."""

from perfbench.harness import delta


def read(run):
    took = delta.modules(run, "step")
    return 1e3 * sum(took) / len(took) if took else None
