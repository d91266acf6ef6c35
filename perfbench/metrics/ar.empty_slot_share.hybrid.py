"""Share of the slots the window's step programs computed that held no row,
read as ``ar.empty_slot_share.sentence`` reads it.  An empty slot of this
backbone still has its recurrent state read and written."""

from perfbench.harness import hybrid

read = hybrid.sibling("ar.empty_slot_share.sentence")
