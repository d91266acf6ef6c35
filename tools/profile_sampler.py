#!/usr/bin/env python3
"""What stands behind the head, alone, on the chip: the head's product and
the choice of an id a row (``models/unit_layers.py:choose``), at the cells'
rows and vocabularies, each candidate a program of its own under one capture,
read by operation (``tools/profile_by_scope.py``).

    python tools/profile_sampler.py [--only PREFIX] [--rehearse] [--out F]

Candidates, each ``head -> ...`` over ``h`` ``[N, H]`` and the head's matrix
``[V, H]``:

- ``before``: what ``sample`` and ``sdar.unmask`` were until PR 46, kept
  here to be measured against: the arg-max, ``jax.random.categorical``
  (a second arg-max), and for a pass ``log_softmax`` of the masked logits
  again, one value a row gathered from it, the logits handed out as
  ``[S, B, V]``;
- ``choose``: ``sample``'s ids for a step, for a pass ``choose``'s ids and
  log-probabilities, the logits handed out as the head wrote them;
- ``choose.rbg``: the same with a key of the ``rbg`` implementation (the
  generator XLA has for the platform in place of threefry's arithmetic);
- ``floor``: the same with no noise drawn: what the read, the comparison
  and the sum cost without the draw's bits.

One JSON line a shape and candidate: the program's device milliseconds a
run, by scope, and its operations.  ``--rehearse`` runs tiny shapes on the
CPU without a capture (shapes and results only).  Needs a TPU otherwise.
"""

from __future__ import annotations

import argparse
import json
import shutil
import sys
import tempfile
from pathlib import Path
from unittest import mock

ROOT = Path(__file__).resolve().parent.parent
sys.path[:0] = [str(ROOT), str(Path(__file__).resolve().parent)]

import jax
import jax.numpy as jnp

from sonata_tpu.models import unit_layers
from sonata_tpu.models.unit_layers import UnitIds

BF16, F32 = jnp.bfloat16, jnp.float32
RUNS = 8
#: cell: rows, block length (0: a step, ids alone), H, V, the unit ids
SHAPES = {
    "sdar_pass": (256, 4, 2048, 151936, UnitIds(256, 151935, 151669)),
    "nemotron_step": (256, 0, 2688, 131072, UnitIds(256, 131071)),
    "laguna_step": (256, 0, 2048, 100352, UnitIds(256, 100351)),
    "lfm2_step": (64, 0, 2048, 65536, UnitIds(256, 65535)),
}
TINY = {"sdar_pass": (8, 4, 64, 640, UnitIds(16, 639, 600)),
        "nemotron_step": (8, 0, 64, 512, UnitIds(16, 511))}


def head(h, w):
    with jax.named_scope("head"):
        return jax.lax.dot_general(h, w, (((1,), (1,)), ((), ())),
                                   preferred_element_type=F32)


def before(logits, temperature, key, units, block: int):
    """``unit_layers.sample`` and the confidence of ``sdar.unmask`` as PR 45
    left them."""
    allowed = unit_layers.allowed_ids(logits.shape[-1], units)
    masked = jnp.where(allowed, logits, -jnp.inf)
    greedy = jnp.argmax(masked, -1)
    safe = jnp.maximum(temperature, 1e-6)[:, None]
    drawn = jax.random.categorical(key, masked / safe, axis=-1)
    ids = jnp.where(temperature > 0, drawn, greedy).astype(jnp.int32)
    if not block:
        return ids, logits
    scale = jnp.where(temperature > 0, temperature, 1.0)
    log_p = jax.nn.log_softmax(masked / scale[:, None], -1)
    confidence = jnp.take_along_axis(log_p, ids[:, None], -1)[:, 0]
    return ids, confidence, logits.reshape(-1, block, logits.shape[-1])


def chosen(logits, temperature, key, units, block: int):
    if not block:
        return unit_layers.sample(logits, temperature, key, units), logits
    ids, confidence = unit_layers.choose(logits, temperature, key, units)
    return ids, confidence, logits


def floor(logits, temperature, key, units, block: int):
    """``chosen`` with zeros where it would draw its noise."""
    with mock.patch.object(jax.random, "gumbel", lambda key, shape, dtype:
                           jnp.zeros(shape, dtype)):
        return chosen(logits, temperature, key, units, block)


CANDIDATES = {"before": (before, None), "choose": (chosen, None),
              "choose.rbg": (chosen, "rbg"), "floor": (floor, None)}


def build(name: str, cand: str, units, block: int):
    behind, impl = CANDIDATES[cand]

    def program(h, w, temperature, step_no):
        key = jax.random.fold_in(
            jax.random.key(0, impl=impl) if impl else jax.random.PRNGKey(0),
            step_no)
        with jax.named_scope("unmask"):
            return behind(head(h, w), temperature, key, units, block)

    program.__name__ = program.__qualname__ = \
        f"{name}__{cand.replace('.', '_')}"
    return jax.jit(program)


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default="")
    ap.add_argument("--rehearse", action="store_true")
    ap.add_argument("--out")
    args = ap.parse_args()
    shapes = TINY if args.rehearse else SHAPES
    lines = []
    for name, (n, block, width, vocab, units) in shapes.items():
        if not name.startswith(args.only):
            continue
        keys = jax.random.split(jax.random.PRNGKey(46), 2)
        h = jax.random.normal(keys[0], (n, width), BF16)
        w = (jax.random.normal(keys[1], (vocab, width), F32)
             * width ** -0.5).astype(BF16)
        temperature = jnp.full((n,), 0.667, F32)
        programs = {c: build(name, c, units, block) for c in CANDIDATES}
        first = {c: jax.block_until_ready(f(h, w, temperature, 0))
                 for c, f in programs.items()}
        same = {c: bool(jnp.array_equal(out[0], first["before"][0]))
                for c, out in first.items()}
        if block:
            err = {c: float(jnp.max(jnp.abs(out[1] - first["before"][1])))
                   for c, out in first.items()}
        del first
        read = {}
        if not args.rehearse:
            import profile_by_scope
            log_dir = tempfile.mkdtemp(prefix="profile_sampler_")
            try:
                jax.profiler.start_trace(log_dir)
                try:
                    for f in programs.values():
                        for i in range(RUNS):
                            jax.block_until_ready(f(h, w, temperature, i))
                finally:
                    jax.profiler.stop_trace()
                read = profile_by_scope.by_scope(log_dir)
            finally:
                shutil.rmtree(log_dir, ignore_errors=True)
        for c, f in programs.items():
            line = {"shape": name, "N": n, "V": vocab, "candidate": c,
                    "ids_as_before": same[c]}
            if block:
                line["confidence_err_max"] = err[c]
            got = read.get("jit_" + f.__name__) or {}
            line.update(ms=got.get("ms_a_run"),
                        scopes=got.get("scopes_ms_a_run"),
                        ops=[[o["op"], round(o["ms_a_run"], 4)]
                             for o in got.get("top_ops", [])[:8]])
            print(json.dumps(line), flush=True)
            lines.append(line)
        del h, w
    if args.out:
        Path(args.out).parent.mkdir(parents=True, exist_ok=True)
        Path(args.out).write_text("\n".join(map(json.dumps, lines)) + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
