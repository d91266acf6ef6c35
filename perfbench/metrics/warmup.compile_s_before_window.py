"""Seconds the server's threads spent on the way from a Python function to
an executable before the window: ``sonata_compile_seconds_total`` as the
window began, every phase (tracing, lowering, and the backend's compile or
its load from the persistent cache), every program, summed over the threads
that compiled (a warm-up on four threads spends four seconds a second)."""

from perfbench.harness import compiles


def read(run):
    return compiles.total(run["metrics_before"], compiles.SECONDS)
