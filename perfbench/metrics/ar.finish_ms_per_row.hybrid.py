"""Milliseconds the step loop's one finisher thread spends on a retired row
once its vocoder program has run, read as ``ar.finish_ms_per_row.sentence``
reads them (four times the rows a second of the sibling cells retire
through the one thread)."""

from perfbench.harness import hybrid

read = hybrid.sibling("ar.finish_ms_per_row.sentence")
