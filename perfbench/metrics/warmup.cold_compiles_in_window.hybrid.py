"""Programs compiled inside the window: the server's own counter of cold
compiles after readiness plus the entries the window added to the
persistent compile cache, read as ``warmup.cold_compiles_in_window.sentence``
reads them."""

from perfbench.harness import hybrid

read = hybrid.sibling("warmup.cold_compiles_in_window.sentence")
