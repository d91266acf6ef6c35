"""Device-to-host transfer of a dispatch's results."""

from __future__ import annotations


def prefetch_to_host(out) -> None:
    """Start the device→host copy of a dispatch's outputs immediately.

    The copy engine runs the D2H transfer as soon as the program
    finishes, overlapping it with whatever computes next; the later
    ``device_get`` then finds the host copy already materialized.
    """
    for a in (out if isinstance(out, (tuple, list)) else (out,)):
        a.copy_to_host_async()
