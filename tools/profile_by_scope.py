#!/usr/bin/env python3
"""A capture's device time by program, and within a program by scope and
by operation: how PERF.md §5's tables of a step, a pass and a prefill are
read.

    JAX_PLATFORMS=cpu python tools/profile_by_scope.py <log_dir> [out.json]

``<log_dir>`` holds the ``.xplane.pb`` of a ``/debug/profile`` capture.  An
operation belongs to the program (``XLA Modules`` event) it started in and
to the innermost of ``SCOPES`` on its scope path (``jax.named_scope``, kept
in the operation's metadata: ``profile_report.op_metadata``); per program:
how often it ran, its milliseconds a run, milliseconds a run by scope, and
its heaviest operations.  A cell's traced run deletes its capture as it
ends (``perfbench/run.py``): copy the file while the check child reads it.
"""

from __future__ import annotations

import bisect
import json
import re
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent))

import profile_report as report

#: the scopes the unit voices' programs name (``models/lfm2.py``, ``sdar.py``,
#: ``nemotron_h.py``, ``pangu_moe.py``, ``gigachat.py``)
SCOPES = ("moe_experts", "moe_route", "shared_expert", "attn_op", "mla_op",
          "ssm_op", "delta_op", "dense_ffn", "conv_op", "head", "unmask")
TOP_OPS = 30


def scope_of(path: str) -> str:
    found = [(m.start(), s) for s in SCOPES
             for m in re.finditer(rf"(?<!\w){s}(?!\w)", path)]
    return max(found)[1] if found else "other"


def program_of(module_name: str) -> str:
    return re.sub(r"\(\d+\)$", "", module_name)


def by_scope(log_dir) -> dict:
    events = report.load(log_dir)
    metadata = report.op_metadata(log_dir)
    planes = sorted({e["plane"] for e in events
                     if report.DEVICE_PLANE.match(e["plane"])})
    if not planes:
        raise ValueError(f"no device plane in the capture under {log_dir}")
    device = [e for e in events if e["plane"] == planes[0]]
    modules = sorted((e for e in device if e["line"] == "XLA Modules"),
                     key=lambda e: e["start_ns"])
    starts = [m["start_ns"] for m in modules]
    programs: dict = {}
    for m in modules:
        p = programs.setdefault(program_of(m["name"]), {
            "runs": 0, "ms": 0.0, "scopes": {}, "ops": {}})
        p["runs"] += 1
        p["ms"] += m["dur_ns"] / 1e6
    for e in device:
        if e["line"] != "XLA Ops":
            continue
        i = bisect.bisect_right(starts, e["start_ns"]) - 1
        if i < 0 or e["start_ns"] > starts[i] + modules[i]["dur_ns"]:
            continue
        p = programs[program_of(modules[i]["name"])]
        scope = scope_of(str(metadata.get(e["name"], {}).get(
            report.SCOPE_STAT, "")))
        ms = e["dur_ns"] / 1e6
        p["scopes"][scope] = p["scopes"].get(scope, 0.0) + ms
        op = p["ops"].setdefault(f"{e['name']} @{scope}", [0, 0.0])
        op[0] += 1
        op[1] += ms
    out = {}
    for name, p in programs.items():
        n = p["runs"]
        heaviest = sorted(p["ops"].items(), key=lambda kv: -kv[1][1])
        out[name] = {
            "runs": n, "ms_a_run": p["ms"] / n,
            "scopes_ms_a_run": {k: v / n for k, v in sorted(
                p["scopes"].items(), key=lambda kv: -kv[1])},
            "top_ops": [{"op": k, "calls_a_run": c / n, "ms_a_run": ms / n}
                        for k, (c, ms) in heaviest[:TOP_OPS]]}
    return out


def main(argv=None) -> int:
    argv = sys.argv[1:] if argv is None else argv
    if not 1 <= len(argv) <= 2:
        print(__doc__, file=sys.stderr)
        return 2
    result = by_scope(argv[0])
    if len(argv) == 2:
        Path(argv[1]).write_text(json.dumps(result, indent=1))
    for name, r in result.items():
        print(name, r["runs"], round(r["ms_a_run"], 3),
              {k: round(v, 3) for k, v in r["scopes_ms_a_run"].items()})
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
