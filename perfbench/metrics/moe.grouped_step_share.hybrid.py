"""Percent of the window's steps whose program ran the expert products by
this repo's grouped matmul: ``moe.grouped_step_share.sentence``'s reader over
this cell's step-group spans (``steps`` where ``expert_matmul`` says
``grouped``; the experts' 1856 columns lie in 1920 lanes for it)."""

from perfbench.harness import hybrid

read = hybrid.sibling("moe.grouped_step_share.sentence")
