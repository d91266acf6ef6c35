"""Host milliseconds of the step loop per step, none of them blocked on the
device, read as ``ar.host_ms_per_step.sentence`` reads them (at 256 slots the
loop walks four times the rows a step)."""

from perfbench.harness import delta

read = delta.sibling("ar.host_ms_per_step.sentence")
