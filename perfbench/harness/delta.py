"""What the readers of a delta-rule (linear attention + latent attention +
experts) voice's metrics share: ``steps.py``'s functions over the step
loop's ``dispatch`` spans as they stand, its two functions over the device
trace again by this backbone's program names (``steps.py`` names
``lfm2_step`` and ``lfm2_prefill`` in a table of its own), the step's cost
from the window's or the traced interval's spans, the latent reader's
device time by its operations' name, and the device time of the operations
that touch a delta-rule state, by the arrays they name."""

from __future__ import annotations

import functools
import math
import re
from pathlib import Path

from perfbench.harness import gigachat_costs, steps
from perfbench.harness.blocks import ratio, sibling  # noqa: F401
from perfbench.harness.steps import groups, total, traced_groups  # noqa: F401

#: the jitted programs' names as the device trace's module line has them
#: (a carrying step is ``gigachat_step_admit``: a step by name)
PROGRAMS = {"step": "gigachat_step", "prefill": "gigachat_prefill",
            "vocode": "unit_vocode"}
#: the latent reader's kernel, as its operations are named in the trace
KERNEL = "latent_attention"
#: what a step group states of the mean step, in ``gigachat_costs``' order
STATED = ("live_slot_steps", "held_experts_touched", "held_assignments",
          "kv_positions")


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


def stated(spans: list) -> list:
    """The step groups of a voice with delta-rule layers that state what
    the share of the experts got (a program without them, as the parent's:
    none)."""
    return [g for g in spans if g.get("delta_layers")
            and all(key in g for key in STATED)]


def mean_step(run, spans: list):
    """Of the mean step of ``spans``: live rows, held experts touched, held
    assignments, positions attended over; or ``None``."""
    spans = stated(spans)
    n = steps.total(spans, "steps")
    if not n:
        return None
    return tuple(steps.total(spans, key) / n for key in STATED)


def mean_step_cost(run, spans: list):
    """``gigachat_costs.step_cost`` of the mean step of ``spans``, or
    ``None``."""
    mean = mean_step(run, spans)
    if mean is None:
        return None
    return gigachat_costs.step_cost(run["dims"]["backbone"], *mean)


@functools.lru_cache(maxsize=2)
def _events_of(log_dir: str):
    """The first device plane's operations of the capture under
    ``log_dir`` (read once a process: two readers share it)."""
    from perfbench.harness import trace as reduction

    events = reduction.load_events(
        log_dir, planes=lambda name: bool(reduction.DEVICE_PLANE.match(name)))
    planes = sorted({e["plane"] for e in events})
    return tuple(e for e in events if e["plane"] == planes[0]
                 and e["line"] == reduction.OPS_LINE) if planes else None


def device_events(run):
    """The first device plane's operations of the traced interval, from the
    raw events while the profile's directory is on disk; ``None`` where
    there is none to read."""
    trace = run.get("trace") or {}
    log_dir = (run.get("profile") or {}).get("log_dir")
    if not trace.get("busy_s") or not log_dir or not Path(log_dir).is_dir():
        return None
    try:
        return _events_of(str(log_dir))
    except Exception:       # no profile to read is nothing to read
        return None


def kernel_seconds(run):
    """Device seconds of the latent reader's kernel in the traced interval,
    by its operations' name; ``None`` where nothing tells the kernel apart
    (an einsum has no name of its own)."""
    mine = [e["dur_ns"] for e in device_events(run) or ()
            if KERNEL in e["name"]]
    return sum(mine) / 1e9 if mine else None


def names_state(text: str, elements: int) -> bool:
    """Whether an operation's text (its result and its operands, as the
    trace prints them) names a float32 array of ``elements`` elements: all
    the slots' states of one linear layer, however the compiler laid their
    dimensions out."""
    return any(math.prod(int(d) for d in dims.split(",")) == elements
               for dims in re.findall(r"f32\[([\d,]+)\]", text))


def state_seconds(run):
    """Device seconds of the operations of the traced interval that touch a
    linear layer's states (every program's reads and writes of one stand
    under ``delta_op``; a reader of keys and values or of latent rows names
    no float32 array of that size), and how many they were; ``None`` where
    the program has no such state."""
    bb = run["dims"]["backbone"]
    slots = int(((run.get("config") or {}).get("server") or {}).get(
        "env", {}).get("SONATA_AR_SLOTS", 0))
    if not slots or "linear_num_value_heads" not in bb:
        return None
    elements = slots * gigachat_costs.sizes(bb)["state"]
    mine = [e["dur_ns"] for e in device_events(run) or ()
            if names_state(e["name"], elements)]
    return (sum(mine) / 1e9, len(mine)) if mine else None
