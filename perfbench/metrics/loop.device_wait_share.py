"""Share of the step loop's wall time that its thread spent blocked until
the step before had run (the step groups' ``device_wait_ms`` over their
``wall_ms``, groups ended in the window): high while the device is the
clock, falling as the host's turn grows toward a step's device time."""

from perfbench.harness import compiles


def read(run):
    groups = compiles.turns(run)
    wall = sum(g["wall_ms"] for g in groups)
    if not wall:
        return None
    return 100.0 * sum(g["device_wait_ms"] for g in groups) / wall
