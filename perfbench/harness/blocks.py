"""What the readers of a block-diffusion voice's metrics share: ``steps.py``'s
functions over the step loop's ``dispatch`` spans as they stand (a span's
attributes do not name a program), and its two functions over the device
trace again, by this backbone's program names (``steps.py`` names
``lfm2_step`` and ``lfm2_prefill`` in a table of its own)."""

from __future__ import annotations

from pathlib import Path

from perfbench.harness import parts, steps
from perfbench.harness.steps import groups, total, traced_groups  # noqa: F401

#: the jitted programs' names as the device trace's module line has them
PROGRAMS = {"step": "sdar_pass", "prefill": "sdar_prefill",
            "vocode": "unit_vocode"}


def sibling(name: str):
    """The ``read`` of the accepted metric ``name``, for a reader of this
    cell that reads the same spans or counters in the same way (a metric is
    one file a name, so the cell's own name needs its own file)."""
    metrics = Path(__file__).resolve().parents[1] / "metrics"
    return parts.load_file(metrics / f"{name}.py").read


def modules(run, kind: str) -> list:
    """Device seconds of each executed program of ``kind`` in the trace."""
    trace = run.get("trace") or {}
    return [m["dur_ns"] / 1e9 for m in trace.get("modules", [])
            if PROGRAMS[kind] in m["name"]]


def device_share(run, kind: str):
    """Percent of the device's busy time that programs of ``kind`` took."""
    trace = run.get("trace") or {}
    if not trace.get("busy_s") or not modules(run, "step"):
        return None
    return 100.0 * sum(modules(run, kind)) / trace["busy_s"]


def ratio(run, over: str, under: str, scale: float = 1.0):
    """An attribute's sum over another's, over the window's step groups
    (a program whose groups lack either, as the loop's were before it
    counted units: nothing to read)."""
    spans = [g for g in steps.groups(run) if over in g and under in g]
    n = steps.total(spans, under)
    return scale * steps.total(spans, over) / n if n else None
