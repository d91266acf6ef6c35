"""Units a live row is left with per pass: the step-group spans' ``units``
over ``live_slot_steps``.  ``block_length / (denoising_steps + 1)`` by the
schedule (4 / 3), less what a prompt's tail takes of a row's first block
and the budget cuts off its last."""

from perfbench.harness import blocks


def read(run):
    return blocks.ratio(run, "units", "live_slot_steps")
