"""Share of the frames the vocoder programs computed that no row needed,
read as ``ar.vocode_padding_share.sentence`` reads it."""

from perfbench.harness import latent

read = latent.sibling("ar.vocode_padding_share.sentence")
