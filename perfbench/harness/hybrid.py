"""What the readers of a hybrid (state-space + attention + experts) voice's
metrics share: ``steps.py``'s functions over the step loop's ``dispatch``
spans as they stand, its two functions over the device trace again by this
backbone's program names (``steps.py`` names ``lfm2_step`` and
``lfm2_prefill`` in a table of its own), and the step's cost from the
window's or the traced interval's spans."""

from __future__ import annotations

from perfbench.harness import nemotron_costs, steps
from perfbench.harness.blocks import ratio, sibling  # noqa: F401
from perfbench.harness.steps import groups, total, traced_groups  # noqa: F401

#: the jitted programs' names as the device trace's module line has them
PROGRAMS = {"step": "nemotron_step", "prefill": "nemotron_prefill",
            "vocode": "unit_vocode"}


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


def mean_step_cost(run, spans: list):
    """``nemotron_costs.step_cost`` of the mean step of ``spans`` (step
    groups that say what the held experts got), or ``None``."""
    spans = [g for g in spans if "held_experts_touched" in g]
    n = steps.total(spans, "steps")
    if not n:
        return None
    return nemotron_costs.step_cost(
        run["dims"]["backbone"], steps.total(spans, "live_slot_steps") / n,
        steps.total(spans, "held_experts_touched") / n,
        steps.total(spans, "held_assignments") / n,
        steps.total(spans, "kv_positions") / n)
