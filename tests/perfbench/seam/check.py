"""The seam configuration's comparison: what a configuration that is not
the benchmark's VITS brings as files alone.  It reads what the harness hands
every comparison (``sampled_spans``, ``work_dir``), runs the VITS comparison
over the reference this configuration names, and returns numbers of its own
names, which its own limits file bounds."""

import json
from pathlib import Path

from perfbench.harness import parts


def compare(job: dict, config: dict) -> dict:
    root, work = Path(job["root"]), Path(job["work_dir"])
    reference = parts.load(root, job["paths"], config, "reference")
    marker = json.loads((work / "voice" / "seam_marker.json").read_text())
    server = json.loads((work / "seam" / "server.json").read_text())
    pointed = str(work / "seam")
    without = 0
    for req in job["sampled"]:
        trace = job["sampled_spans"].get(req["rid"], {"spans": []})
        without += not any(
            s["name"] == "dispatch"
            and req["rid"] in s.get("attrs", {}).get("request_ids", ())
            for s in trace["spans"])
    vits = parts.load_file(root / parts.DEFAULTS["check"]).compare(job,
                                                                   config)
    numbers = dict(
        vits["numbers"],
        seam_audio_err_max=vits["numbers"]["audio_err_max"],
        seam_replayed_without_dispatch=without,
        seam_files_astray=(
            (getattr(reference, "NAME", None) != "seam")
            + (marker["weights_seed"] != config["weights"]["seed"])
            + (server["argv_dir"] != pointed)
            + (server["env_dir"] != pointed)))
    info = dict(vits["info"], seam_span_names=sorted(
        {s["name"] for t in job["sampled_spans"].values()
         for s in t["spans"]}), seam_spans_of=sorted(job["sampled_spans"]))
    return {"numbers": numbers, "info": info}
