"""The server under test, run in a process the benchmark can read.

    python -m perfbench.harness.serve <server module> <its arguments ...>

Runs the named module's ``main`` with the arguments unchanged: the stock
command line, the stock process.  When ``main`` has returned (the server
drains on SIGTERM and comes back), the runtime's own memory counters of
every local device are written to the file ``PERFBENCH_MEMORY_STATS``
names.  Only the process that holds the chip can read them, and the
server's ``/metrics`` exports one of them (``peak_bytes_in_use``), which
leaves out what its programs reserve for their temporaries.
"""

from __future__ import annotations

import importlib
import json
import os
import sys


def memory_stats() -> list:
    import jax

    return [{"device": str(d), "stats": d.memory_stats()}
            for d in jax.local_devices()]


def main(argv: list) -> int:
    module = importlib.import_module(argv[1])
    try:
        return int(module.main(argv[2:]) or 0)
    finally:
        out = os.environ.get("PERFBENCH_MEMORY_STATS")
        if out:
            with open(out + ".tmp", "w") as f:
                json.dump(memory_stats(), f)
            os.replace(out + ".tmp", out)


if __name__ == "__main__":
    sys.exit(main(sys.argv))
