"""The seam configuration's writer: a file outside ``perfbench/`` that the
configuration names.  It writes the tiny VITS voice through the benchmark's
own writer and leaves a marker of its own beside it, and it describes a
voice whose path has no frame-budget estimator."""

import json
from pathlib import Path

from perfbench.harness import voicegen

reference_params = voicegen.reference_params


def write_voice(out_dir, config: dict) -> Path:
    path = voicegen.write_voice(out_dir, config)
    (Path(out_dir) / "seam_marker.json").write_text(json.dumps(
        {"writer": __file__, "weights_seed": config["weights"]["seed"]}))
    return path


def describe(config: dict) -> dict:
    return dict(voicegen.describe(config), frame_budget_estimator=False)
