"""What the readers of a voice that mixes full and window attention share:
``steps.py``'s functions over the step loop's ``dispatch`` spans as they
stand, its two functions over the device trace again by this backbone's
program names (``steps.py`` names ``lfm2_step`` and ``lfm2_prefill`` in a
table of its own), the step's cost from the window's or the traced
interval's spans, and the cache reader's device time by its operations'
name."""

from __future__ import annotations

from pathlib import Path

from perfbench.harness import laguna_costs, steps
from perfbench.harness.blocks import ratio, sibling  # noqa: F401
from perfbench.harness.steps import groups, total, traced_groups  # noqa: F401

#: the jitted programs' names as the device trace's module line has them
#: (a carrying step is ``laguna_step_admit``: a step by name)
PROGRAMS = {"step": "laguna_step", "prefill": "laguna_prefill",
            "vocode": "unit_vocode"}
#: the reader of both kinds of cache, as its operations are named in the
#: trace
KERNEL = "slot_attention"
#: what a step group states of the mean step, in ``laguna_costs``' order
STATED = ("live_slot_steps", "held_experts_touched", "held_assignments",
          "kv_positions", "kv_cache_bytes")


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


def windowed(spans: list) -> list:
    """The step groups of a voice with window layers that state what its
    caches cost (a program without them, as the parent's: none)."""
    return [g for g in spans if g.get("window_layers")
            and all(key in g for key in STATED)]


def mean_step(run, spans: list):
    """Of the mean step of ``spans``: live rows, held experts touched, held
    assignments, positions attended over, bytes of keys and values read as
    held; or ``None``."""
    spans = windowed(spans)
    n = steps.total(spans, "steps")
    if not n:
        return None
    return tuple(steps.total(spans, key) / n for key in STATED)


def mean_step_cost(run, spans: list):
    """``laguna_costs.step_cost`` of the mean step of ``spans``, or
    ``None``."""
    mean = mean_step(run, spans)
    if mean is None:
        return None
    return laguna_costs.step_cost(run["dims"]["backbone"], *mean)


def kernel_seconds(run):
    """Device seconds of the cache reader's kernel in the traced interval,
    by its operations' name: the sum over ``trace.device_ops`` where all of
    the kernel's operations stand there (the list is the ten heaviest, a
    layer's reader an operation of its own), else from the raw events while
    the profile's directory is on disk; ``None`` where neither tells the
    kernel apart (an einsum has no name of its own)."""
    trace = run.get("trace") or {}
    layers = int(run["dims"]["backbone"].get("num_hidden_layers", 0))
    listed = [s for label, s in trace.get("device_ops", [])
              if KERNEL in label]
    if layers and len(listed) >= layers:
        return sum(listed)
    log_dir = (run.get("profile") or {}).get("log_dir")
    if not trace.get("busy_s") or not log_dir or not Path(log_dir).is_dir():
        return None
    try:
        from perfbench.harness import trace as reduction

        events = reduction.load_events(
            log_dir, planes=lambda name: bool(
                reduction.DEVICE_PLANE.match(name)))
    except Exception:       # no profile to read is nothing to read
        return None
    planes = sorted({e["plane"] for e in events})
    mine = [e["dur_ns"] for e in events if e["plane"] == planes[0]
            and e["line"] == reduction.OPS_LINE and KERNEL in e["name"]] \
        if planes else []
    return sum(mine) / 1e9 if mine else None
