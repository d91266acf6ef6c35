"""Percent of the live rows' steps whose position was at or past the
window: the step-group spans' ``window_bound_row_steps`` over their
``live_slot_steps`` (the ring had wrapped, and the band, not the row's
length, bounded what a window layer read).  A program whose spans state no
such count: nothing."""

from perfbench.harness import windowed


def read(run):
    return windowed.ratio(run, "window_bound_row_steps", "live_slot_steps",
                          100.0)
