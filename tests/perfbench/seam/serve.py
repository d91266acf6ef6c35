"""The seam configuration's server command: says where the harness pointed
it (``{work_dir}`` in ``server.argv`` and ``server.env``), then runs the
stock server as ``perfbench.harness.serve`` does.

    python tests/perfbench/seam/serve.py <dir> <server module> <arguments>
"""

import json
import os
import sys
from pathlib import Path


def main(argv: list) -> int:
    sys.path.insert(0, os.getcwd())     # spawned from the root of a checkout
    from perfbench.harness import serve

    out = Path(argv[1])
    out.mkdir(parents=True, exist_ok=True)
    (out / "server.json").write_text(json.dumps(
        {"argv_dir": argv[1], "env_dir": os.environ.get("SEAM_SERVER_DIR")}))
    return serve.main(argv[1:])


if __name__ == "__main__":
    sys.exit(main(sys.argv))
