"""Percent of the window's steps whose program ran the expert products by
this repo's grouped matmul: ``moe.grouped_step_share.sentence``'s reader over
this cell's step-group spans (``steps`` where ``expert_matmul`` says
``grouped``: the short path of a thin share, 256 rows on 8 experts; a launch
that overflowed it is counted by the spans' ``held_overflow_steps``)."""

from perfbench.harness import delta

read = delta.sibling("moe.grouped_step_share.sentence")
