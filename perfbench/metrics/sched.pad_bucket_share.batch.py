"""Share of the frames computed in the window between a group's frame
budget and its frame bucket (``sonata_dispatch_frames_total{part="bucket"}``):
the ladder's step."""

from perfbench.harness import counters


def read(run):
    return counters.frame_share(run, ["bucket"])
