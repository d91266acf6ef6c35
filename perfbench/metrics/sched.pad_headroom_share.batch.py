"""Share of the frames computed in the window between a group's longest
row and its frame budget (``sonata_dispatch_frames_total{part="headroom"}``):
the estimator's running maximum and its safety factor."""

from perfbench.harness import counters


def read(run):
    return counters.frame_share(run, ["headroom"])
