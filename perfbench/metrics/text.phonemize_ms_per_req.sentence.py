"""Host milliseconds of the text stage per request: the ``phonemize`` and
``encode-ids`` spans of the window's requests."""


def read(run):
    spans = [s for s in run["spans"]
             if s["name"] in ("phonemize", "encode-ids")]
    requests = sum(1 for s in spans if s["name"] == "phonemize")
    if not requests:
        return None
    return sum(s["end"] - s["start"] for s in spans) * 1e3 / requests
