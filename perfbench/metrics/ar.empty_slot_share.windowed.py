"""Share of the slots the window's step programs computed that held no row,
read as ``ar.empty_slot_share.sentence`` reads it.  An empty slot of this
backbone still runs both kinds of attention and the head."""

from perfbench.harness import windowed

read = windowed.sibling("ar.empty_slot_share.sentence")
