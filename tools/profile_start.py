#!/usr/bin/env python3
"""What a start pays on the host for a unit voice's step programs, on the
CPU, without a chip: seconds of trace and of lower, and the equations
traced and lowered, for the step and each carrying step of a cell's
backbone, compiled for a described v5e at the cell's size.

    python tools/profile_start.py lfm2_step [sdar_pass ...] [--reps N]

No cache holds a trace or a lowering: a warm start of ``lfm2-24b-a2b`` spends
41 of its 70 thread-seconds on them (PERF.md section 6, PR 38), under the
GIL, and the chip's host takes two to three times this machine's seconds.
A start's programs are taken in a start's order behind ``jax.clear_caches()``
(the first pays the trace of what the later ones share: a jitted kernel is
traced once a process and lowered once a program); a program's seconds are
the least of ``--reps`` such rounds.  The seconds swing by a quarter from
run to run on a shared machine; the equations do not: ``eqns_lowered``
counts a jaxpr a program calls twice once, ``eqns_traced`` a jaxpr an
earlier program traced not at all.  ``jaxpr`` hashes a program's text, to
show that a change leaves a program as it was.  To compare two trees, run
the tool from each (``git archive`` the other into a directory).  One JSON
line a backbone (names: ``tests/test_compiled_for_v5e.py:CELLS``).
"""

from __future__ import annotations

import argparse
import hashlib
import importlib
import json
import os
import sys
import time
from pathlib import Path

import jax
import jax.numpy as jnp

ROOT = Path(__file__).resolve().parent.parent

#: the text buckets a slot of 1024 positions holds: a carrying step each
TEXT_BUCKETS = (16, 32, 64, 96, 128, 192, 256)


def inner_jaxprs(eqn):
    """The jaxprs among an equation's parameters (a jitted function's, a
    loop's body, a kernel's)."""
    for param in eqn.params.values():
        for inner in param if isinstance(param, (list, tuple)) else [param]:
            inner = getattr(inner, "jaxpr", inner)
            if hasattr(inner, "eqns"):
                yield inner


def equations(jaxpr, seen=None) -> int:
    """The equations of ``jaxpr`` and of every jaxpr under them; with
    ``seen`` (a dict, kept by the caller) a jaxpr met before, a jitted
    function called again, counts nothing more."""
    if seen is not None:
        if id(jaxpr) in seen:
            return 0
        seen[id(jaxpr)] = jaxpr     # held: an id is not given out again
    return sum(1 + sum(equations(inner, seen) for inner in inner_jaxprs(eqn))
               for eqn in jaxpr.eqns)


def programs(name: str, one_chip):
    """``(tag, jitted program, argument shapes)`` of a start of the cell
    ``name``, the rules steered as on a TPU (``test_compiled_for_v5e``)."""
    from sonata_tpu.models import unit_backbone
    cells = importlib.import_module("test_compiled_for_v5e")
    cells.gm._tiles_here = cells.gm.tile_rule
    cells.sa._tiles_here = cells.sa.tile_rule
    cells.sa._latent_tiles_here = cells.sa.latent_tile_rule
    unit_backbone._layers_once_here = lambda: unit_backbone.LAYERS_ONCE
    backbone, _, args = cells.step_shapes(name, one_chip)
    yield "step", backbone.build_step, args
    if name == "sdar_pass":     # its prompts are prefilled apart
        return
    for t in TEXT_BUCKETS:
        arrival = (jax.ShapeDtypeStruct((t,), jnp.int32), *(
            jax.ShapeDtypeStruct((), kind) for kind in (
                jnp.int32, jnp.int32, jnp.float32, jnp.int32)))
        yield f"admit{t}", backbone.build_step_admit, args + tuple(
            jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip)
            for a in arrival)


def measure(name: str, one_chip, reps: int) -> dict:
    out: dict = {}
    for rep in range(reps):
        jax.clear_caches()
        traced_before: dict = {}
        for tag, build, args in programs(name, one_chip):
            clock = time.perf_counter()
            traced = build().trace(*args)
            trace_s = time.perf_counter() - clock
            clock = time.perf_counter()
            traced.lower()
            lower_s = time.perf_counter() - clock
            jaxpr = traced.jaxpr.jaxpr
            line = {"trace_s": trace_s, "lower_s": lower_s,
                    "eqns_traced": equations(jaxpr, traced_before),
                    "eqns_lowered": equations(jaxpr, {}),
                    "jaxpr": hashlib.sha256(
                        str(traced.jaxpr).encode()).hexdigest()[:16]}
            was = out.get(tag)
            if was:
                for key in ("trace_s", "lower_s"):
                    line[key] = min(line[key], was[key])
            out[tag] = line
    total = {key: round(sum(p[key] for p in out.values()), 3)
             for key in ("trace_s", "lower_s", "eqns_traced",
                         "eqns_lowered")}
    return {"backbone": name, "tree": str(ROOT), **total, "programs": out}


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("names", nargs="+")
    ap.add_argument("--reps", type=int, default=3)
    args = ap.parse_args()
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    sys.path[:0] = [str(ROOT), str(ROOT / "tests")]
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    jax.config.update("jax_enable_compilation_cache", False)
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    one_chip = SingleDeviceSharding(topo.devices[0])
    for name in args.names:
        print(json.dumps(measure(name, one_chip, args.reps)), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
