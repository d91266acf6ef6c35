"""Reduction of a profiler trace to the device's numbers.

``load_events`` reads the ``.xplane.pb`` that ``jax.profiler`` wrote (it
needs jax, so it runs in the check process); everything after it works on
plain dicts, so the reduction is tested on a small recorded trace
(``tests/perfbench/data/trace_events.json``).

An event: ``{"plane", "line", "name", "start_ns", "dur_ns", "stats"}``.
Device planes are named ``/device:TPU:<n>``; their ``XLA Ops`` line holds
one event per executed HLO operation and their ``XLA Modules`` line one per
executed program.

    python -m perfbench.harness.trace <log_dir>     # summary, for a look
"""

from __future__ import annotations

import json
import re
import sys
from pathlib import Path

DEVICE_PLANE = re.compile(r"^/device:(TPU|GPU):\d+")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"


def find_xplane(log_dir) -> Path:
    files = sorted(Path(log_dir).rglob("*.xplane.pb"))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return files[-1]


def load_events(log_dir, planes=None) -> list:
    from jax.profiler import ProfileData

    data = ProfileData.from_file(str(find_xplane(log_dir)))
    events = []
    for plane in data.planes:
        if planes is not None and not planes(plane.name):
            continue
        for line in plane.lines:
            for ev in line.events:
                stats = {}
                for key, value in ev.stats:
                    if isinstance(value, (int, float, str)):
                        stats[key] = value
                events.append({"plane": plane.name, "line": line.name,
                               "name": ev.name,
                               "start_ns": float(ev.start_ns),
                               "dur_ns": float(ev.duration_ns),
                               "stats": stats})
    return events


def union_length(intervals: list) -> float:
    total, end = 0.0, None
    for a, b in sorted(intervals):
        if end is None or a > end:
            total += b - a
            end = b
        elif b > end:
            total += b - end
            end = b
    return total


def gaps(intervals: list, lo: float, hi: float) -> list:
    """Idle intervals ``(start, end)`` of ``[lo, hi]`` not covered."""
    out, end = [], lo
    for a, b in sorted(intervals):
        if a > end:
            out.append((end, a))
        end = max(end, b)
    if hi > end:
        out.append((end, hi))
    return out


def op_label(ev: dict) -> str:
    """An operation as the trace prints it: ``%name = type[shape]``, the
    layout and the operands left out."""
    m = re.match(r"^(%?[\w.\-]+ = \(?[a-z]+\d*\[[\d,]*\])", ev["name"])
    return m.group(1) if m else ev["name"][:80]


def largest_dim(ev: dict) -> tuple:
    """``(largest dimension, leading dimension of that shape)`` over the
    shapes an operation's statistics mention."""
    best = (0, 0)
    for text in [ev["name"].split(" fusion(")[0].split("(")[0]] + list(
            ev["stats"].values()):
        if isinstance(text, str):
            for dims in re.findall(r"\[([\d,]+)\]", text[:400]):
                sizes = [int(d) for d in dims.split(",") if d]
                if sizes and max(sizes) > best[0]:
                    best = (max(sizes), sizes[0])
    return best


def reduce_events(events: list, spans: list = ()) -> dict:
    """busy / window seconds, the heaviest operations, the longest idle
    gaps, and one record per executed program.

    ``spans``: ``(name, start_ns, end_ns)`` of host-side spans on the
    trace's clock; an idle gap is attributed to the span that covers most
    of it."""
    device = sorted({e["plane"] for e in events
                     if DEVICE_PLANE.match(e["plane"])})
    if not device:
        return {}
    ops = [e for e in events if e["plane"] in device
           and e["line"] == OPS_LINE]
    if not ops:
        return {}
    lo = min(e["start_ns"] for e in ops)
    hi = max(e["start_ns"] + e["dur_ns"] for e in ops)
    busy, totals = [], {}
    for plane in device:
        mine = [e for e in ops if e["plane"] == plane]
        if not mine:
            continue
        busy.append(union_length(
            [(e["start_ns"], e["start_ns"] + e["dur_ns"]) for e in mine]))
        for e in mine:
            label = op_label(e)
            totals[label] = totals.get(label, 0.0) + e["dur_ns"]
    first = [e for e in ops if e["plane"] == device[0]]
    idle = sorted(gaps([(e["start_ns"], e["start_ns"] + e["dur_ns"])
                        for e in first], lo, hi),
                  key=lambda g: g[0] - g[1])[:10]

    def owner(a: float, b: float) -> str:
        best, best_cover = "unattributed", 0.0
        for name, s, t in spans:
            cover = min(b, t) - max(a, s)
            if cover > best_cover:
                best, best_cover = name, cover
        return best

    modules = []
    for plane in device[:1]:
        for m in sorted((e for e in events if e["plane"] == plane
                         and e["line"] == MODULES_LINE),
                        key=lambda e: e["start_ns"]):
            a, b = m["start_ns"], m["start_ns"] + m["dur_ns"]
            inside = [e for e in first if a <= e["start_ns"] < b]
            big = max([largest_dim(e) for e in inside] or [(0, 0)])
            modules.append({
                "name": m["name"], "start_ns": a, "dur_ns": m["dur_ns"],
                "ops": len(inside), "largest_dim": big[0], "batch": big[1]})
    n = len(busy)
    return {
        "devices": n,
        "busy_s": sum(busy) / n / 1e9,
        "window_s": (hi - lo) / 1e9,
        "device_ops": [[k, v / 1e9 / n] for k, v in sorted(
            totals.items(), key=lambda kv: -kv[1])[:10]],
        "idle_gaps": [[owner(a, b), (b - a) / 1e9] for a, b in idle],
        "modules": modules,
        "t0_ns": lo,
    }


def fit_clock(module_ends: list, span_ends: list, lo: float = -6e9,
              hi: float = 1e9) -> float:
    """The shift (ns) that puts wall-clock span times on the trace's
    clock: ``trace = wall + shift``.  The trace's clock starts when the
    profiler does, some time after the request for a profile was sent, so
    the shift is found from the data: a dispatch span ends just after its
    program does, so the shift is the one that brings the spans' ends
    nearest to the programs' ends."""
    import numpy as np

    m = np.asarray(sorted(module_ends), float)
    e = np.asarray(sorted(span_ends), float)
    if not len(m) or not len(e):
        return 0.0

    def cost(shift: float) -> float:
        at = e + shift
        i = np.clip(np.searchsorted(at, m), 1, len(at) - 1)
        return float(np.sum(np.minimum(np.abs(m - at[i - 1]),
                                       np.abs(at[i] - m))))

    best = min(np.arange(lo, hi, 5e6), key=cost)
    return float(min(np.arange(best - 5e6, best + 5e6, 2e5), key=cost))


def reduce_profile(job: dict) -> dict:
    """The reduction of the server's own profile, with the server's spans
    (wall-clock seconds) put on the trace's clock."""
    events = load_events(job["log_dir"],
                         planes=lambda name: bool(DEVICE_PLANE.match(name)))
    wall0 = job["profile"]["wall_start"]
    modules = [e for e in events if e["line"] == MODULES_LINE
               and e["dur_ns"] > 5e6]
    dispatch = [s for s in job.get("spans", []) if s["name"] == "dispatch"]
    shift = fit_clock([e["start_ns"] + e["dur_ns"] for e in modules],
                      [(s["end"] - wall0) * 1e9 for s in dispatch])
    spans = [(s["name"], (s["start"] - wall0) * 1e9 + shift,
              (s["end"] - wall0) * 1e9 + shift)
             for s in job.get("spans", [])]
    out = reduce_events(events, spans)
    if out:
        out["events"] = len(events)
        out["clock_shift_s"] = shift / 1e9
        out["wall_t0"] = wall0 + (out["t0_ns"] - shift) / 1e9
    return out


def thin(events: list, until_ns: float, shortest_ns: float = 5e4) -> list:
    """A small recorded trace for the tests: the device's programs and
    operations of the first ``until_ns``, operations shorter than
    ``shortest_ns`` left out, names cut."""
    keep = []
    for e in events:
        if not DEVICE_PLANE.match(e["plane"]) or e["start_ns"] > until_ns:
            continue
        if e["line"] == MODULES_LINE or (e["line"] == OPS_LINE
                                         and e["dur_ns"] >= shortest_ns):
            keep.append(dict(e, name=e["name"][:160], stats={}))
    return keep


def main(argv: list) -> int:
    events = load_events(argv[1])
    if len(argv) > 2:
        Path(argv[2]).write_text(json.dumps(thin(events, float(argv[3]))))
        return 0
    seen = {}
    for e in events:
        seen.setdefault((e["plane"], e["line"]), []).append(e)
    for (plane, line), evs in sorted(seen.items()):
        print(f"{plane} | {line}: {len(evs)} events")
        for e in evs[:2]:
            print("   ", json.dumps(e)[:600])
    summary = reduce_events(events)
    summary["modules"] = summary.get("modules", [])[:12]
    print(json.dumps(summary, indent=1)[:6000])
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv))
