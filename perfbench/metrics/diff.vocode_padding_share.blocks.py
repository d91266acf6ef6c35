"""Share of the frames the vocoder programs computed that no row needed
(``1 - sum(frames_needed) / sum(frames_bucket)`` over the window's ``kind:
vocode`` dispatch spans): a retired row runs alone at its frame bucket here
as in the sibling cell, whose ``ar.vocode_padding_share.sentence`` reads
it."""

from perfbench.harness import blocks

read = blocks.sibling("ar.vocode_padding_share.sentence")
