"""Share of the traced interval in which no operation ran on the device, read
as ``device.idle_share.sentence`` reads it."""

from perfbench.harness import latent

read = latent.sibling("device.idle_share.sentence")
