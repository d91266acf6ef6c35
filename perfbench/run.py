#!/usr/bin/env python3
"""One run of one cell of the benchmark.

    python3 perfbench/run.py --workload <name> --seed <n> --seconds <s> \
        --trace <0|1>

Everything about a cell is data found by name in ``BENCHMARK.json``: the
configuration's file and the files it names (the voice's writer, the
reference, the comparison: ``harness/parts.py``), its limits,
``<path>/traffic/<traffic>.json`` and, for a traced run,
``<path>/metrics/<metric>.py``.  This process never imports jax: the
server child holds the chip during the window, the check child after it.
The last line of standard output is the result; a run that finds no
accelerator, too few chips or a broken harness prints none and exits
non-zero.
"""

from __future__ import annotations

import argparse
import json
import os
import shutil
import subprocess
import sys
import tempfile
import threading
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from perfbench.harness import parts  # noqa: E402
from perfbench.harness import server as srv  # noqa: E402

ACCELERATORS = ("tpu", "gpu")


class HarnessError(RuntimeError):
    pass


def load_cell(benchmark: dict, workload: str, root: Path) -> dict:
    cells = {w["name"]: w for w in benchmark["workloads"]}
    if workload not in cells:
        raise HarnessError(f"no workload {workload!r} in the benchmark "
                           f"(known: {sorted(cells)})")
    cell = cells[workload]
    entry = next(c for c in benchmark["configs"]
                 if c["name"] == cell["config"])
    paths = benchmark["paths"]
    traffic_file = parts.find(root, paths, "traffic",
                              f"{cell['traffic']}.json")
    if traffic_file is None:
        raise HarnessError(f"no traffic file for {cell['traffic']!r}")

    def applies(metric: dict) -> bool:
        return "workloads" not in metric or workload in metric["workloads"]

    return {"cell": cell, "config_entry": entry,
            "config": json.loads((root / entry["file"]).read_text()),
            "traffic": json.loads(traffic_file.read_text()),
            "end_to_end": [m for m in benchmark["end_to_end"] if applies(m)],
            "per_layer": [m for m in benchmark["per_layer"] if applies(m)],
            "paths": paths}


def cache_entries(root: Path) -> int:
    cache = Path(os.environ.get("JAX_COMPILATION_CACHE_DIR")
                 or root / ".jax_cache")
    return sum(1 for p in cache.rglob("*") if p.is_file()) \
        if cache.is_dir() else 0


class LineReader(threading.Thread):
    """Collects a child's ``TAG {json}`` lines."""

    def __init__(self, proc):
        super().__init__(daemon=True)
        self.proc, self.lines, self.cond = proc, {}, threading.Condition()
        self.start()

    def run(self):
        for raw in self.proc.stdout:
            tag, _, rest = raw.partition(" ")
            if tag in ("WARM", "DONE", "CHECK"):
                with self.cond:
                    self.lines[tag] = json.loads(rest)
                    self.cond.notify_all()
        with self.cond:
            self.lines["EOF"] = True
            self.cond.notify_all()

    def wait_for(self, tag: str, timeout_s: float):
        deadline = time.monotonic() + timeout_s
        with self.cond:
            while tag not in self.lines:
                left = deadline - time.monotonic()
                if "EOF" in self.lines or left <= 0:
                    return None
                self.cond.wait(min(left, 1.0))
            return self.lines[tag]


def spawn_module(module: str, job: dict, work: Path, root: Path, env=None):
    job_file = work / f"{module.rsplit('.', 1)[-1]}_job.json"
    job_file.write_text(json.dumps(job))
    err = open(work / f"{module.rsplit('.', 1)[-1]}.err", "w")
    return subprocess.Popen(
        [sys.executable, "-m", module, str(job_file)], cwd=root, env=env,
        stdout=subprocess.PIPE, stderr=err, text=True), err


def spans_on_wall_clock(traces: list, names=("phonemize", "encode-ids",
                                             "dispatch",
                                             "stream-emit")) -> list:
    """Server spans with their start and end in wall-clock seconds."""
    out = []
    for t in traces:
        for s in t["spans"]:
            if s["name"] in names and "duration_ms" in s:
                a = t["wall_start"] + s["start_ms"] / 1e3
                out.append({"name": s["name"], "start": a,
                            "end": a + s["duration_ms"] / 1e3,
                            "attrs": s.get("attrs") or {}})
    return out


def run_cell(workload: str, seed: int, seconds: float, trace: bool,
             benchmark_file: Path = None, root: Path = ROOT,
             platform: str = "tpu", require_accelerator: bool = True,
             alter_audio=None) -> dict:
    """The whole run; returns the result object of the last line.

    ``platform`` is what the children's ``JAX_PLATFORMS`` is set to, so a
    missing chip is JAX's own start-up error.  ``require_accelerator`` and
    ``alter_audio`` exist for the tests that drive the rest of a run on the
    CPU and break the timed path underneath."""
    benchmark = json.loads((benchmark_file
                            or root / "BENCHMARK.json").read_text())
    cell = load_cell(benchmark, workload, root)
    config, traffic = cell["config"], cell["traffic"]
    limits = parts.load_limits(root, cell["paths"], cell["cell"]["config"])
    writer = parts.load(root, cell["paths"], config, "writer")
    voice = writer.describe(config)
    work = Path(tempfile.mkdtemp(prefix="perfbench_"))
    server = gen = check = profile = None
    files = []
    env = dict(os.environ, JAX_PLATFORMS=platform)
    env.pop("BENCH_RUN", None)
    try:
        t_setup = time.monotonic()
        voice_path = writer.write_voice(work / "voice", config)
        server = srv.Server(root, config, str(voice_path), platform, work)
        server.wait_ready(900.0)
        device = server.device()
        if require_accelerator and device["platform"] not in ACCELERATORS:
            raise HarnessError(f"the server runs on {device['platform']}, "
                               "not on an accelerator")
        if device["count"] < cell["cell"]["chips"]:
            raise HarnessError(f"{cell['cell']['chips']} chips asked for, "
                               f"{device['count']} present")
        gen, err = spawn_module("perfbench.harness.loadgen", {
            "root": str(root), "grpc_port": server.grpc_port,
            "voice_path": str(voice_path), "traffic": traffic, "seed": seed,
            "seconds": seconds, "out_dir": str(work),
            "voice": voice}, work, root, env)
        files.append(err)
        lines = LineReader(gen)
        warm = lines.wait_for(
            "WARM", float(traffic["warmup"]["max_seconds"]) + 60.0)
        if warm is None:
            raise HarnessError("the generator never announced the window:\n"
                               + (work / "loadgen.err").read_text()[-2000:]
                               + server.log_text()[-2000:])
        setup_s = time.monotonic() - t_setup
        metrics_before, entries_before = server.metrics(), cache_entries(root)
        if trace:
            time.sleep(min(seconds / 4.0, 5.0))
            profile = server.profile(max(min(seconds / 3.0, 4.0), 0.5))
        done = lines.wait_for("DONE", seconds + 900.0)
        gen.wait(timeout=60.0)
        if done is None or "error" in done:
            raise HarnessError(f"the generator failed: {done}\n"
                               + (work / "loadgen.err").read_text()[-2000:])
        metrics_after, entries_after = server.metrics(), cache_entries(root)
        # after the window and the replay: what the server recorded of the
        # replayed requests goes to the comparison in every run
        traces = server.traces()
        replayed = {r["rid"] for r in done["sampled"]}
        sampled_spans = {t["request_id"]: {"wall_start": t["wall_start"],
                                           "spans": t["spans"]}
                         for t in traces if t["request_id"] in replayed}
        server.stop()
        # the runtime counts live arrays and what programs reserve for
        # their temporaries apart, each with a peak of its own; the larger
        # of the two on the fullest chip is a peak that was certainly
        # reached (the true one lies between it and their sum)
        peaks = server.memory_peaks()
        peak = None if peaks is None else max(
            max(d["in_use"], d["reserved"]) for d in peaks)

        if alter_audio is not None:
            alter_audio(done)
        wall0 = done["wall_origin"] + done["t_window"]
        spans = [s for s in spans_on_wall_clock(traces)
                 if wall0 <= s["end"] <= wall0 + seconds]
        check_job = {
            "root": str(root), "paths": cell["paths"],
            "config_file": cell["config_entry"]["file"],
            "seed": seed, "words": traffic["words"],
            "sampled": done["sampled"],
            "sampled_audio": done["sampled_audio"],
            "sampled_spans": sampled_spans, "work_dir": str(work),
            "speaker": done["speaker"], "limits": limits,
            "rows": traffic["check"].get("rows")}
        if profile is not None:
            check_job["trace"] = {"log_dir": profile["log_dir"],
                                  "spans": spans, "profile": profile}
        check, err = spawn_module("perfbench.reference.check", check_job,
                                  work, root, env)
        files.append(err)
        result = LineReader(check).wait_for("CHECK", 1100.0)
        check.wait(timeout=60.0)
        if result is None:
            raise HarnessError("the comparison gave no result:\n"
                               + (work / "check.err").read_text()[-3000:])
        if result["device"]["platform"] != device["platform"] or \
                result["device"]["count"] != device["count"]:
            raise HarnessError(f"the server ran on {device}, the check on "
                               f"{result['device']}")

        numbers = result.get("numbers", {})
        compared = {k: {"value": numbers.get(k), "limit": limits[k]}
                    for k in limits}
        correct = bool(numbers) and done["failed"] == 0 \
            and done["completed"] > 0 \
            and all(r["ok"] for r in done["sampled"]) and all(
                c["value"] is not None and c["value"] <= c["limit"]
                for c in compared.values())
        run = {"workload": workload, "seed": seed, "seconds": seconds,
               "config": config, "dims": voice["dims"],
               "traffic": traffic, "generator": done, "warm": warm,
               "setup_s": setup_s, "metrics_before": metrics_before,
               "metrics_after": metrics_after, "spans": spans,
               "trace": result.get("trace"), "profile": profile,
               "device": result["device"],
               "cache_entries_added": entries_after - entries_before}
        wanted = cell["per_layer"] if trace else cell["end_to_end"]
        metrics = {}
        for m in wanted:
            if m["name"] == "setup_s":
                value = setup_s
            else:
                value = parts.load_reader(root, cell["paths"],
                                          m["name"])(run)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        dev_out = dict(result["device"], memory_peak_bytes=peak)
        out = {"correct": correct, "attempted": done["attempted"],
               "failed": done["failed"], "metrics": metrics,
               "device": dev_out}
        reduced = result.get("trace") or {}
        if trace and device["platform"] in ACCELERATORS \
                and not reduced.get("busy_s"):
            raise HarnessError(
                "the traced interval holds no device operation: "
                f"profile {profile}, {len(done['records'])} requests in "
                "the window\n" + server.log_text()[-3000:])
        if trace and reduced.get("busy_s"):
            dev_out["busy_s"] = reduced["busy_s"]
            dev_out["window_s"] = reduced["window_s"]
            out["breakdown"] = {"device_ops": reduced["device_ops"],
                                "idle_gaps": reduced["idle_gaps"]}
        took = sorted(r["t_end"] - r["t_start"] for r in done["records"])
        out["info"] = dict(result.get("info", {}), warmup=warm,
                           latency_s=[took[len(took) // 2],
                                      took[int(len(took) * 0.95)],
                                      took[-1]] if took else None,
                           completed=done["completed"],
                           answers_per_second=done["answers_per_second"],
                           sampled_seqs=[r["seq"] for r in done["sampled"]],
                           audio_s=done["audio_s"],
                           memory_peaks=peaks,
                           request_errors=[r.get("error") for r in
                                           done["records"]
                                           if not r["ok"]][:3])
        if "estimator_replay" in done:
            out["info"]["estimator_replay"] = done["estimator_replay"]
        out["compared"] = compared
        return out
    finally:
        for proc in (gen, check):
            if proc is not None and proc.poll() is None:
                proc.kill()
                proc.wait()
        if server is not None:
            server.stop()
        for f in files:
            f.close()
        if profile is not None:
            shutil.rmtree(profile["log_dir"], ignore_errors=True)
        shutil.rmtree(work, ignore_errors=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        out = run_cell(args.workload, args.seed, args.seconds,
                       bool(args.trace))
    except (HarnessError, RuntimeError, OSError, KeyError) as e:
        sys.stderr.write(f"perfbench: no result: {type(e).__name__}: {e}\n")
        return 1
    for name, c in out["compared"].items():
        sys.stderr.write(f"compared {name} = {c['value']} "
                         f"(limit {c['limit']})\n")
    sys.stderr.flush()
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
