"""Share of the frames computed in the window by programs that clipped a row
and were run again in a larger bucket
(``sonata_dispatch_frames_total{part="retried"}``).  A reader for the tests,
as ``sched.pad_dummy_rows_share.batch``."""

from perfbench.harness import counters


def read(run):
    return counters.frame_share(run, ["retried"])
