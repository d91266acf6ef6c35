"""Share of the slots the window's step programs computed that held no row,
read as ``ar.empty_slot_share.sentence`` reads it.  An empty slot of this
backbone still moves its delta-rule states and runs the mixers' projections
and the head."""

from perfbench.harness import delta

read = delta.sibling("ar.empty_slot_share.sentence")
