"""Percent of the window's passes whose program ran the expert products by
this repo's grouped matmul: the sibling cell's reader over this cell's
step-group spans (``steps`` where ``expert_matmul`` says ``grouped``)."""

from perfbench.harness import blocks

read = blocks.sibling("moe.grouped_step_share.sentence")
