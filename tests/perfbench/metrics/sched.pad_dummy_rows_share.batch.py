"""Share of the frames computed in the window that are whole dummy rows, put
in to fill a batch bucket (``sonata_dispatch_frames_total{part="dummy_rows"}``).
A reader for the tests: with ``sched.pad_retried_share.batch`` it closes the
split of ``sched.frame_padding_share.batch`` into its five causes, which
holds in any window, quiet or not."""

from perfbench.harness import counters


def read(run):
    return counters.frame_share(run, ["dummy_rows"])
