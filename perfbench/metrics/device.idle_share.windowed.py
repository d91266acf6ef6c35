"""Share of the traced interval in which no operation ran on the device, read
as ``device.idle_share.sentence`` reads it."""

from perfbench.harness import windowed

read = windowed.sibling("device.idle_share.sentence")
