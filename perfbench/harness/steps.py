"""What the readers of a step-wise voice's metrics share: the step loop's
own ``dispatch`` spans (``kind: step``, one per group of steps, on a trace
the loop owns) and the three device programs by name.

A reader of spans covers what ``run["spans"]`` holds: spans that *ended*
inside the window.  ``/metrics`` covers more (warm-up, the drain after the
window and the replayed rows, at falling occupancy), so occupancy and
expert load are read here, from spans, and not from the counters."""

from __future__ import annotations

from perfbench.harness import shapes

#: the jitted programs' names as the device trace's module line has them
PROGRAMS = {"step": "lfm2_step", "prefill": "lfm2_prefill",
            "vocode": "unit_vocode"}


def dispatches(run, kind: str, inside=None) -> list:
    """Attributes of the window's ``dispatch`` spans of ``kind`` (``step``,
    ``prefill``, ``vocode``), or of those that ended inside ``inside`` (a
    wall-clock interval)."""
    out = []
    for s in run.get("spans") or []:
        if s["name"] == "dispatch" and s["attrs"].get("kind") == kind \
                and (inside is None or inside[0] <= s["end"] <= inside[1]):
            out.append(s["attrs"])
    return out


def groups(run, inside=None) -> list:
    """The step-group spans' attributes."""
    return dispatches(run, "step", inside)


def total(spans: list, key: str) -> float:
    """Sum of an attribute over the groups (a per-layer list is summed
    over the layers)."""
    values = [g[key] for g in spans]
    return float(sum(sum(v) if isinstance(v, list) else v for v in values))


def modules(run, kind: str) -> list:
    """Device seconds of each executed program of ``kind`` in the trace."""
    trace = run.get("trace") or {}
    return [m["dur_ns"] / 1e9 for m in trace.get("modules", [])
            if PROGRAMS[kind] in m["name"]]


def device_share(run, kind: str):
    """Percent of the device's busy time that programs of ``kind`` took."""
    trace = run.get("trace") or {}
    took = modules(run, kind)
    if not trace.get("busy_s") or not modules(run, "step"):
        return None
    return 100.0 * sum(took) / trace["busy_s"]


def traced_groups(run) -> list:
    if not (run.get("trace") or {}).get("window_s"):
        return []
    return groups(run, shapes.traced_interval(run))
