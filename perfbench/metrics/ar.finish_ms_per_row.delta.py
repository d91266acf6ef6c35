"""Milliseconds the step loop's one finisher thread spends on a retired row
once its vocoder program has run, read as ``ar.finish_ms_per_row.sentence``
reads them."""

from perfbench.harness import delta

read = delta.sibling("ar.finish_ms_per_row.sentence")
