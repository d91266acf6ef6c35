#!/usr/bin/env python3
"""A unit voice's start, shape by shape, with the device's memory beside it.

    python3 tools/warm_lattice.py <configuration.json> [--steps 20]

Places the configuration's voice as its server command does (the writer's
recipe expanded on the device), speaks one utterance as the server does
before it warms (the step loop and its cache stand from there on), then
warms the lattice through ``serving/warmup.warm_model_lattice`` with its
workers, and prints for every shape when it began, when it was warm or why
it failed, on which thread, and the runtime's memory counters at that
moment; then, if every shape warmed, the host's clock around ``--steps``
steps of every slot.  Exit code 1 if a shape failed.

What it is for: a start that dies in the warm-up costs a whole benchmark
run on the chip (the harness waits 900 s for a readiness that never comes,
and the server's log keeps the *last* failure, which is a consequence: a
dispatch that failed holds its cache by its traceback, and every later
``new_cache`` is refused); this says which shape failed first and what the
device held, in the minutes the compiles take.  On the chip (PR 48: the
cell of 256 slots whose cache is 4.9 GB beside 6.6 GB of weights):

    chiprun --timeout 900 -- python3 tools/warm_lattice.py \\
        perfbench/configs/gigachat/gigachat3.5-432b-a28b.json

Here, with ``JAX_PLATFORMS=cpu`` and a tiny configuration
(``tests/perfbench/data/gigachat-tiny.json``), it rehearses the path; the
CPU keeps no memory counters and the seconds say nothing of a device.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import tempfile
import threading
import time
import traceback
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("config", help="a unit voice's configuration file")
    ap.add_argument("--steps", type=int, default=20)
    args = ap.parse_args(argv)
    sys.path.insert(0, str(ROOT))
    config = json.loads(Path(args.config).read_text())
    for key, value in config["server"]["env"].items():
        if key.startswith("SONATA_AR_") and "{" not in value:
            os.environ.setdefault(key, value)

    import jax
    import numpy as np

    from perfbench.harness import parts
    from sonata_tpu.models import from_config_path
    from sonata_tpu.serving import warmup

    began = time.monotonic()

    def say(what: str) -> None:
        stats = jax.local_devices()[0].memory_stats() or {}
        held = {k: round(v / 1e9, 3) for k, v in stats.items()
                if "bytes" in k and isinstance(v, int)}
        print(f"{time.monotonic() - began:7.1f} s "
              f"{threading.current_thread().name}: {what} {held}",
              flush=True)

    writer = parts.load_file(ROOT / config["writer"])
    serve = parts.load_file(ROOT / config["server"]["argv"][0])
    path = writer.write_voice(Path(tempfile.mkdtemp()) / "voice", config)
    say("start")
    serve.place(path)
    say("weights placed")
    voice = from_config_path(path)
    voice.speak_batch(list(voice.phonemize_text("Ready.")))
    say("spoke once")
    print("warm-up dispatches that may hold a cache at once:",
          voice._warm_cache_slots()._value, "; the loop stands:",
          voice._loop is not None, flush=True)
    warm, failed = voice.warm_shape, []

    def logged(shape) -> None:
        say(f"begin {shape}")
        try:
            warm(shape)
        except BaseException as e:
            failed.append(shape)
            say(f"FAILED {shape}: {type(e).__name__}: {str(e)[:300]}")
            traceback.print_exc(limit=4)
            raise
        say(f"warm  {shape}")

    voice.warm_shape = logged
    try:
        warmed = warmup.warm_model_lattice(
            voice, mode="full", deadline=time.monotonic() + 1800.0)
        print("shapes warm:", warmed, flush=True)
    except Exception as e:
        print("the lattice failed:", type(e).__name__, str(e)[:300],
              flush=True)
    say("after the lattice")
    if failed:
        print("failed first:", failed[0], flush=True)
        return 1
    cache = voice.new_cache()
    live = np.ones((voice.slots,), bool)
    temperature = np.full((voice.slots,), 0.667, np.float32)
    for k in range(3):
        cache = voice.step(cache, live, temperature, k)[0]
    jax.block_until_ready(cache)
    t0 = time.monotonic()
    for k in range(args.steps):
        cache = voice.step(cache, live, temperature, 3 + k)[0]
    jax.block_until_ready(cache)
    print(f"a step of {voice.slots} live rows at their first positions: "
          f"{(time.monotonic() - t0) / args.steps * 1e3:.2f} ms (host "
          f"clock, {args.steps} steps)", flush=True)
    say("end")
    voice.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())
