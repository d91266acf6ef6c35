"""Milliseconds the step loop's one finisher thread spends on a retired row
once its vocoder program has run (the ``kind: vocode`` dispatch spans'
``finish_ms``), read as the sibling cell's ``ar.finish_ms_per_row.sentence``
reads it."""

from perfbench.harness import blocks

read = blocks.sibling("ar.finish_ms_per_row.sentence")
