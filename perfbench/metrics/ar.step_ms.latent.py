"""Device milliseconds of a step program (``pangu_step``, and
``pangu_step_admit`` where a launch carried an arrival), the mean over the
traced interval."""

from perfbench.harness import latent


def read(run):
    took = latent.modules(run, "step")
    return 1e3 * sum(took) / len(took) if took else None
