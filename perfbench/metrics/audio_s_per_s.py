"""Seconds of audio in all responses completed in the window, over the
window (the generator's own clock and sample counts)."""


def read(run):
    gen = run["generator"]
    return gen["audio_s"] / float(gen["seconds"])
