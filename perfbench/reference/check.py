"""The check child: a process of its own that takes the chip once the window
has closed and the server has gone.  It names the device, reduces the device
trace of a traced run (the same for every configuration) and hands the job
to the comparison that the configuration names.

    python -m perfbench.reference.check <job.json>

**The protocol.**  A configuration's ``check`` key names a file (a path
from the root, under a directory of the benchmark's ``paths``; absent: the
VITS comparison, ``harness/parts.py:DEFAULTS``) with one function,

    compare(job, config) -> {"numbers": {...}, "info": {...}}

``config`` is the configuration's file, parsed.  ``job`` is what
``run.py`` wrote:

| key | what it holds |
| --- | --- |
| ``root``, ``paths`` | the checkout, and the benchmark's ``paths`` (``harness/parts.py`` loads the configuration's ``reference`` and ``writer`` from them) |
| ``config_file``, ``seed``, ``words`` | the configuration's file from the root, the run's ``--seed``, the traffic's word list |
| ``sampled`` | the replayed requests, at least one: ``seq``, ``paragraph``, ``rid``, ``ok``, ``sentences`` |
| ``sampled_audio`` | an ``.npz`` of the int16 samples served for them, ``<seq>_<sentence>`` |
| ``sampled_spans`` | ``{rid: {"wall_start", "spans": [...]}}``: the server's own trace of each replayed request, every span, attributes whole (a ``rid`` the server kept no trace of is absent) |
| ``work_dir`` | the run's work directory, which ``server.argv`` / ``server.env`` may point the server at (``{work_dir}``) for what is too bulky for a span; the writer's ``out_dir`` is ``<work_dir>/voice`` |
| ``speaker``, ``rows``, ``limits`` | the speaker a run drew (or null), the traffic's ``check.rows``, the limits as ``run.py`` will apply them |

``numbers`` are held against the limits by name (``run.py``: a limit whose
number is missing or null fails the run); ``info`` goes into the result's
``info`` as it is.  The comparison runs the reference here, on this
process's device, and implements its own controls (``PERFBENCH_CONTROL``).
Neither it nor a writer imports jax while it is loaded: ``run.py`` loads
writers and never touches jax, and this process sets the compile cache
before anything compiles.
"""

from __future__ import annotations

import json
import os
import sys
from pathlib import Path

from perfbench.harness import parts, trace


def run_check(job: dict) -> dict:
    import jax

    root = Path(job["root"])
    cache = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(
        root / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", cache)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.5)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)

    dev = jax.devices()
    out = {"device": {"platform": dev[0].platform,
                      "kind": dev[0].device_kind, "count": len(dev)}}
    if job.get("trace"):
        out["trace"] = trace.reduce_profile(job["trace"])
    if not any(req["ok"] for req in job.get("sampled") or []):
        out["numbers"] = {}
        out["error"] = "no request was sampled for the comparison"
        return out
    config = json.loads((root / job["config_file"]).read_text())
    compared = parts.load(root, job["paths"], config, "check").compare(
        job, config)
    out["numbers"], out["info"] = compared["numbers"], compared["info"]
    return out


def main(argv: list) -> int:
    job = json.loads(Path(argv[1]).read_text())
    print("CHECK " + json.dumps(run_check(job)), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv))
