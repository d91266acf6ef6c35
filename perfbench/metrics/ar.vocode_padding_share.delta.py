"""Share of the frames the vocoder programs computed that no row needed,
read as ``ar.vocode_padding_share.sentence`` reads it."""

from perfbench.harness import delta

read = delta.sibling("ar.vocode_padding_share.sentence")
