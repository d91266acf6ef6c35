"""Share of the traced dispatches that ran in the largest frame bucket
seen in the run.  Two runs of one cell that differ here ran in different
regimes of the frame-budget estimator."""

from perfbench.harness import shapes


def read(run):
    programs = shapes.programs(run)
    if not programs:
        return None
    top = max(p["f"] for p in programs)
    return 100.0 * sum(1 for p in programs if p["f"] == top) / len(programs)
