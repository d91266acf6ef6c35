"""Percent of the window's steps whose program ran the expert products by
this repo's grouped matmul: ``moe.grouped_step_share.sentence``'s reader over
this cell's step-group spans (``steps`` where ``expert_matmul`` says
``grouped``: the short path of a thin share, 640 rows on 32 experts; a launch
that overflowed it is counted by the spans' ``held_overflow_steps``)."""

from perfbench.harness import windowed

read = windowed.sibling("moe.grouped_step_share.sentence")
