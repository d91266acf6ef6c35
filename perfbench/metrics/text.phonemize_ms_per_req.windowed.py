"""Host milliseconds of the text stage per request, read as
``text.phonemize_ms_per_req.sentence`` reads them."""

from perfbench.harness import windowed

read = windowed.sibling("text.phonemize_ms_per_req.sentence")
