"""Host milliseconds of the text stage per request, read as
``text.phonemize_ms_per_req.sentence`` reads them."""

from perfbench.harness import delta

read = delta.sibling("text.phonemize_ms_per_req.sentence")
