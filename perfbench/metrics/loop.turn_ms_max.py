"""The longest turn of the step loop (launch to launch) among the step
groups ended in the window, in milliseconds: a step, a few prefills and a
vocoder's launch in a quiet window; a stall of the loop's thread where there
was one (the group's ``turn_max_phase`` and ``turn_max_step`` say which
phase held it, at which step)."""

from perfbench.harness import compiles


def read(run):
    groups = compiles.turns(run)
    if not groups:
        return None
    return float(max(g["turn_ms_max"] for g in groups))
