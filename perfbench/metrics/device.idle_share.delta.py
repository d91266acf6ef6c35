"""Share of the traced interval in which no operation ran on the device, read
as ``device.idle_share.sentence`` reads it."""

from perfbench.harness import delta

read = delta.sibling("device.idle_share.sentence")
