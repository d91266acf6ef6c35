"""Share of the slots the window's step programs computed that held no row,
read as ``ar.empty_slot_share.sentence`` reads it.  An empty slot of this
backbone still runs latent attention's projections and the head."""

from perfbench.harness import latent

read = latent.sibling("ar.empty_slot_share.sentence")
