"""Share of the live rows' passes that commit a block: the step-group
spans' ``commit_row_passes`` over ``live_slot_steps`` (1 in
``denoising_steps + 1``).  A commit pass chooses nothing: its head and its
logits are computed and unused."""

from perfbench.harness import blocks


def read(run):
    return blocks.ratio(run, "commit_row_passes", "live_slot_steps", 100.0)
