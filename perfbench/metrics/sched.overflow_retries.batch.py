"""Device groups of the window whose frame bucket was too small, so that
the program ran twice (``sonata_dispatch_overflow_retries_total``)."""

from perfbench.harness import counters


def read(run):
    return counters.window(run, "sonata_dispatch_overflow_retries_total")
