"""What the readers of the program's own counters share: a counter's
growth over the window, read from ``/metrics`` before and after it (the
whole window, not the few seconds a device trace covers).  Series are
chosen by name and by a substring of their labels, as the registry may add
labels."""

from __future__ import annotations

FRAMES = "sonata_dispatch_frames_total"
FRAME_PARTS = ("served", "ragged", "headroom", "bucket", "dummy_rows",
               "retried")


def window(run, name: str, label: str = ""):
    """How much the series of ``name`` whose labels contain ``label`` grew
    in the window, or ``None`` where the server exports no such series (a
    program from before the counter)."""
    def total(metrics: dict):
        values = [v for k, v in metrics.items()
                  if (k == name or k.startswith(name + "{")) and label in k]
        return sum(values) if values else None

    after = total(run["metrics_after"])
    if after is None:
        return None
    return after - (total(run["metrics_before"]) or 0.0)


def frame_share(run, parts) -> float:
    """Percent of the frames the window's programs computed that fall
    under ``parts`` (``sonata_dispatch_frames_total``'s causes), or
    ``None`` where nothing was counted."""
    frames = {p: window(run, FRAMES, f'part="{p}"') for p in FRAME_PARTS}
    if any(v is None for v in frames.values()):
        return None
    computed = sum(frames.values())
    if not computed:
        return None
    return 100.0 * sum(frames[p] for p in parts) / computed
