"""Share of the frames computed in the window that pad rows up to their
group's longest row (``sonata_dispatch_frames_total{part="ragged"}``):
what a planner that groups rows of like length would win."""

from perfbench.harness import counters


def read(run):
    return counters.frame_share(run, ["ragged"])
