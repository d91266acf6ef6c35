#!/usr/bin/env python3
"""What one ``/debug/profile`` capture says, read by name and by id.

    python tools/profile_report.py <log_dir> [--traces traces.json]
                                             [--profile profile.json]

The server mirrors its spans into the profiler's trace as ``sonata:<name>``
events carrying ``request_id`` / ``dispatch_id``, and its device program
names its stages (``jax.named_scope``), so a capture is read without
guessing shapes or fitting clocks:

- ``annotations``: the ``sonata:`` events by name;
- ``loop_turns``: a step loop's ``sonata:admit | launch | retire | settle``
  (a unit voice's; they carry ``step_no``): per phase the count, the sum and
  the longest with its ``step_no``, and the longest turn (one ``step_no``'s
  phases from the first's start to the last's end), so a stall of the
  loop's thread lies beside the device's operations on one clock;
- ``stages_s``: device seconds by stage, from the scope path that the
  device plane keeps in each operation's *metadata* (statistic ``tf_op``,
  beside ``hlo_category``, ``flops`` and ``bytes_accessed``;
  ``metadata_example`` shows one);
- ``fetch_after_program_ms``: how long after its program's end each
  ``sonata:fetch`` ended;
- with ``--traces`` (the body of ``/debug/traces``) and ``--profile`` (the
  body of ``/debug/profile``): ``clock``, the shift that puts the wall
  clock on the trace's clock, three ways: from the anchors, from joining
  ``dispatch`` spans to ``sonata:epilogue`` events by ``dispatch_id``, and
  (where ``perfbench`` is importable) from ``fit_clock``.

Needs jax to read the ``.xplane.pb``; runs on any backend.
"""

from __future__ import annotations

import argparse
import json
import re
import statistics
import sys
import warnings
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent
#: the statistic of an operation's metadata that holds its scope path
SCOPE_STAT = "tf_op"
DEVICE_PLANE = re.compile(r"^/device:(TPU|GPU):\d+")
#: stage of a device operation, by the first pattern its scope path holds
#: the phases of a step loop's turn, as the loop mirrors them
LOOP_PHASES = ("sonata:admit", "sonata:launch", "sonata:retire",
               "sonata:settle")
STAGES = (("decode/pre", "decode/pre"), ("decode/post", "decode/post"),
          (r"decode/ups\d+", None), ("decode", "decode (other)"),
          ("epilogue", "epilogue"), ("flow_reverse", "flow_reverse"),
          ("acoustics", "acoustics (other)"),
          ("duration_predictor", "duration_predictor"),
          ("encode_text", "encode_text (other)"))


def find_xplane(log_dir) -> Path:
    files = sorted(Path(log_dir).rglob("*.xplane.pb"))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return files[-1]


def load(log_dir) -> list:
    """Every event of the newest ``.xplane.pb`` under ``log_dir``:
    ``{"plane", "line", "name", "start_ns", "dur_ns", "stats"}``."""
    from jax.profiler import ProfileData

    events = []
    with warnings.catch_warnings():
        # reading an event's statistics warns once per event (a builtin
        # type of the profiler's bindings has no __module__)
        warnings.simplefilter("ignore", DeprecationWarning)
        for plane in ProfileData.from_file(
                str(find_xplane(log_dir))).planes:
            for line in plane.lines:
                for ev in line.events:
                    events.append({
                        "plane": plane.name, "line": line.name,
                        "name": ev.name, "start_ns": float(ev.start_ns),
                        "dur_ns": float(ev.duration_ns),
                        "stats": {k: v for k, v in ev.stats
                                  if isinstance(v, (int, float, str))}})
    return events


def annotations(events: list) -> list:
    """The program's own events, ids as strings (the profiler turns an id
    made of digits into a number)."""
    out = []
    for e in events:
        if e["name"].startswith("sonata:"):
            out.append(dict(e, ids={k: str(v) for k, v in e["stats"].items()
                                    if k in ("request_id", "dispatch_id",
                                             "step_no")}))
    return sorted(out, key=lambda e: e["start_ns"])


def loop_turns(notes: list):
    """What the step loop's mirrored phases say (``notes``:
    :func:`annotations`), or ``None`` where the capture holds none."""
    phases = [e for e in notes
              if e["name"] in LOOP_PHASES and "step_no" in e["ids"]]
    if not phases:
        return None
    out, turns = {}, {}
    for e in phases:
        acc = out.setdefault(e["name"][len("sonata:"):],
                             {"count": 0, "total_ms": 0.0, "max_ms": 0.0,
                              "max_step_no": None})
        acc["count"] += 1
        acc["total_ms"] += e["dur_ns"] / 1e6
        if e["dur_ns"] / 1e6 > acc["max_ms"]:
            acc.update(max_ms=e["dur_ns"] / 1e6,
                       max_step_no=int(e["ids"]["step_no"]))
        turn = turns.setdefault(int(e["ids"]["step_no"]),
                                [e["start_ns"], e["start_ns"]])
        turn[0] = min(turn[0], e["start_ns"])
        turn[1] = max(turn[1], e["start_ns"] + e["dur_ns"])
    step_no, (a, b) = max(turns.items(), key=lambda t: t[1][1] - t[1][0])
    return {"phases": out, "turns": len(turns),
            "longest_turn": {"step_no": step_no, "ms": (b - a) / 1e6}}


def device_ops(events: list) -> list:
    planes = sorted({e["plane"] for e in events
                     if DEVICE_PLANE.match(e["plane"])})
    return [e for e in events if planes and e["plane"] == planes[0]
            and e["line"] == "XLA Ops"]


def stage_of(path: str) -> str:
    for pattern, label in STAGES:
        m = re.search(r"(?<![\w])" + pattern + r"(?![\w])", path)
        if m:
            return label or m.group(0)
    return "unnamed"


def _varint(buf, i: int) -> tuple:
    value = shift = 0
    while True:
        byte = buf[i]
        i += 1
        value |= (byte & 0x7F) << shift
        if byte < 0x80:
            return value, i
        shift += 7


def _fields(buf):
    """``(field number, value)`` of one protobuf message: an int for a
    varint, the bytes for everything else."""
    i, n = 0, len(buf)
    while i < n:
        tag, i = _varint(buf, i)
        wire = tag & 7
        if wire == 0:
            value, i = _varint(buf, i)
        else:
            if wire == 2:
                size, i = _varint(buf, i)
            elif wire in (1, 5):
                size = 8 if wire == 1 else 4
            else:
                raise ValueError(f"wire type {wire} in an xplane file")
            value, i = buf[i:i + size], i + size
        yield tag >> 3, value


def op_metadata(log_dir) -> dict:
    """``{event name: {statistic: value}}`` of the first device plane's
    event *metadata*.  This runtime keeps an operation's scope path
    (``tf_op``), its category, operations and bytes there, once per
    distinct operation, and not among the statistics of each event, which
    is all ``jax.profiler.ProfileData`` hands out; so the file's few
    fields that matter are decoded here (``XSpace.planes`` ->
    ``XPlane.event_metadata`` / ``stat_metadata`` -> ``XStat``)."""
    data = memoryview(find_xplane(log_dir).read_bytes())
    for field, plane in _fields(data):
        if field != 1:
            continue
        parts = list(_fields(plane))
        name = next((bytes(v).decode() for f, v in parts if f == 2), "")
        if not DEVICE_PLANE.match(name):
            continue
        stat_names, out = {}, {}
        for f, entry in parts:
            if f == 5:      # map<int64, XStatMetadata>: id 1, name 2
                meta = dict(_fields(dict(_fields(entry))[2]))
                stat_names[meta.get(1, 0)] = bytes(meta.get(2, b"")).decode()
        for f, entry in parts:
            if f != 4:      # map<int64, XEventMetadata>: name 2, stats 5
                continue
            event_name, stats = "", {}
            for g, value in _fields(dict(_fields(entry))[2]):
                if g == 2:
                    event_name = bytes(value).decode()
                elif g == 5:
                    stat = dict(_fields(value))
                    key = stat_names.get(stat.get(1))
                    if 5 in stat:                       # str_value
                        stats[key] = bytes(stat[5]).decode()
                    elif 3 in stat or 4 in stat:        # (u)int64_value
                        stats[key] = stat.get(3, stat.get(4))
            out[event_name] = stats
        return out
    return {}


def stage_seconds(ops: list, metadata: dict) -> dict:
    """Device seconds by stage, each operation's scope path taken from
    its metadata's ``tf_op``."""
    out = {}
    for e in ops:
        stage = stage_of(str(metadata.get(e["name"], {}).get(SCOPE_STAT, "")))
        out[stage] = out.get(stage, 0.0) + e["dur_ns"] / 1e9
    return dict(sorted(out.items(), key=lambda kv: -kv[1]))


def programs(events: list, shortest_ns: float = 5e6) -> list:
    planes = sorted({e["plane"] for e in events
                     if DEVICE_PLANE.match(e["plane"])})
    return sorted((e for e in events if planes and e["plane"] == planes[0]
                   and e["line"] == "XLA Modules"
                   and e["dur_ns"] > shortest_ns),
                  key=lambda e: e["start_ns"])


def fetch_after_program_ms(events: list) -> list:
    """For each ``sonata:fetch``: its end minus the end of the last
    program that ended inside it."""
    ends = [p["start_ns"] + p["dur_ns"] for p in programs(events)]
    out = []
    for f in annotations(events):
        if f["name"] != "sonata:fetch":
            continue
        a, b = f["start_ns"], f["start_ns"] + f["dur_ns"]
        inside = [t for t in ends if a <= t <= b]
        if inside:
            out.append((b - max(inside)) / 1e6)
    return out


def clock(events: list, traces: list, profile: dict) -> dict:
    """``trace = wall + shift`` (seconds), wall times counted from the
    ``start_called`` anchor."""
    anchors = profile["anchors"]
    wall0 = anchors["start_called"]["wall"]
    out = {"anchors_shift_s": [
        -(anchors["start_returned"]["wall"] - wall0), 0.0]}
    epilogue_end = {}
    for e in annotations(events):
        if e["name"] == "sonata:epilogue" and "dispatch_id" in e["ids"]:
            epilogue_end[e["ids"]["dispatch_id"]] = max(
                epilogue_end.get(e["ids"]["dispatch_id"], 0.0),
                e["start_ns"] + e["dur_ns"])
    joined, span_ends = [], []
    for t in traces:
        for s in t["spans"]:
            if s["name"] != "dispatch" or "duration_ms" not in s:
                continue
            end = t["wall_start"] + (s["start_ms"]
                                     + s["duration_ms"]) / 1e3 - wall0
            span_ends.append(end * 1e9)
            did = str((s.get("attrs") or {}).get("dispatch_id"))
            if did in epilogue_end:
                joined.append(epilogue_end[did] / 1e9 - end)
    if joined:
        out["joined_shift_s"] = {"median": statistics.median(joined),
                                 "min": min(joined), "max": max(joined),
                                 "dispatches": len(joined)}
    try:
        if str(ROOT) not in sys.path:   # run as a script: tools/ is first
            sys.path.insert(0, str(ROOT))
        from perfbench.harness.trace import fit_clock
    except ImportError:
        return out
    ends = [p["start_ns"] + p["dur_ns"] for p in programs(events)]
    if ends and span_ends:
        out["fit_clock_shift_s"] = fit_clock(ends, span_ends) / 1e9
    return out


def report(log_dir, traces=None, profile=None) -> dict:
    events = load(log_dir)
    by_name = {}
    notes = annotations(events)
    for e in notes:
        by_name.setdefault(e["name"], []).append(e)
    ops = device_ops(events)
    metadata = op_metadata(log_dir) if ops else {}
    after = fetch_after_program_ms(events)
    out = {
        "events": len(events),
        "annotations": {k: {"count": len(v), "example_ids": v[0]["ids"],
                            "median_ms": statistics.median(
                                e["dur_ns"] for e in v) / 1e6}
                        for k, v in sorted(by_name.items())},
        "loop_turns": loop_turns(notes),
        "device_ops": len(ops),
        "metadata_example": metadata.get(ops[len(ops) // 2]["name"])
        if ops else None,
        "stages_s": stage_seconds(ops, metadata) if ops else {},
        "fetch_after_program_ms": {
            "count": len(after), "median": statistics.median(after),
            "min": min(after), "max": max(after)} if after else None,
    }
    if traces is not None and profile is not None:
        out["clock"] = clock(events, traces, profile)
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("log_dir")
    ap.add_argument("--traces", help="file holding /debug/traces' body")
    ap.add_argument("--profile", help="file holding /debug/profile's body")
    args = ap.parse_args(argv)
    traces = profile = None
    if args.traces and args.profile:
        traces = json.loads(Path(args.traces).read_text())["traces"]
        profile = json.loads(Path(args.profile).read_text())
    print(json.dumps(report(args.log_dir, traces, profile), indent=1))
    return 0


if __name__ == "__main__":
    sys.exit(main())
