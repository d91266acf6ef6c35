"""What the readers of the program's compile events and of the step loop's
turns share.  The events are counters (``sonata_compile_total`` and
``sonata_compile_seconds_total``, by ``program``, ``phase``, ``cache`` and
``stage``), read from ``/metrics`` as the window began (everything the start
compiled, traced, lowered or loaded) and after the replay; the turns are
attributes of the step groups' ``dispatch`` spans.  A program from before
either reads as ``None``."""

from __future__ import annotations

from perfbench.harness import steps

COUNT = "sonata_compile_total"
SECONDS = "sonata_compile_seconds_total"


def total(metrics: dict, name: str, *labels: str):
    """Sum of the series of ``name`` whose labels hold every one of
    ``labels``, or ``None`` where the page has no series of that name."""
    series = {k: v for k, v in metrics.items() if k.startswith(name + "{")}
    if not series:
        return None
    return sum(v for k, v in series.items() if all(l in k for l in labels))


def turns(run) -> list:
    """Attributes of the window's step groups that carry the loop's account
    of its turns."""
    return [g for g in steps.groups(run) if "wall_ms" in g]
