"""Host milliseconds of the text stage per request: the ``phonemize`` and
``encode-ids`` spans of the window's requests, read as the sibling cell's
``text.phonemize_ms_per_req.sentence`` reads them."""

from perfbench.harness import blocks

read = blocks.sibling("text.phonemize_ms_per_req.sentence")
