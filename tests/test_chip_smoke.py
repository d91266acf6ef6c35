"""``chip_smoke.py`` end to end in rehearsal mode: the same phases the chip
run makes (voice writer, the real server CLI driven over the wire, SIGTERM
drain, log scan, warm second boot) with a tiny voice on the CPU."""

import json
import os
import subprocess
import sys
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parent.parent


@pytest.mark.slow
def test_chip_smoke_rehearsal_passes_and_names_the_platform(tmp_path):
    env = dict(os.environ, JAX_COMPILATION_CACHE_DIR=str(tmp_path / "jc"))
    env.pop("XLA_FLAGS", None)  # conftest's 8 virtual devices: one is enough
    proc = subprocess.run(
        [sys.executable, str(REPO / "chip_smoke.py"), "--rehearse",
         "--lattice", "minimal"],
        env=env, cwd=REPO, capture_output=True, text=True, timeout=1200)
    assert proc.returncode == 0, proc.stdout[-4000:] + proc.stderr[-4000:]
    last = json.loads(proc.stdout.strip().splitlines()[-1])
    assert last == {"ok": True, "device": {"platform": "cpu",
                                           "kind": "cpu", "count": 1}}
    assert "REHEARSAL" in proc.stdout
    summary = json.loads(
        (REPO / "chiprun_out" / "chip_smoke" / "summary.json").read_text())
    assert summary["cache_dir"] == str(tmp_path / "jc")
    assert summary["boot1"]["lattice_shapes_warmed"] > 0
    assert summary["boot1"]["drain_phases"][-1] == "done"
    assert summary["boot2"]["time_to_ready_s"] > 0
