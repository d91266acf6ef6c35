"""CPU-backend regression harness.

Runs ``bench.py`` and ``bench_streaming.py`` on the host CPU backend
(``JAX_PLATFORMS=cpu`` in each child's environment) — clearly labeled as
such — with A/B toggles over the optimization stack.  Its numbers are CPU
timings and counts, never device metrics:

- batch RTF: sub-pixel transposed convs (default) vs the naive
  ``lhs_dilation`` lowering (``SONATA_TCONV=naive``), the bfloat16
  decoder compute policy (``SONATA_COMPUTE_DTYPE=bfloat16``)
- batch RTF also covers the int8 weight-only decoder arm
  (``SONATA_DECODE_QUANT=int8``) next to bf16 — both parity-gated by
  tests (bf16: test_vits_model.py; int8: test_decode_opts.py)
- streaming TTFB/throughput: the backend-adaptive dispatch policy's
  default (``auto`` → per-request dispatch on CPU) vs coalescing forced
  on (``SONATA_DISPATCH_POLICY=on``, the pre-policy default shape) vs
  per-request dispatch forced (``SONATA_DISPATCH_POLICY=off``) — the
  last two bracket what the policy chooses between — plus the ISSUE-11
  precision/fusion arms (``SONATA_FUSED_EPILOGUE=off``,
  ``SONATA_DECODE_QUANT=int8``, ``SONATA_COMPUTE_DTYPE=bfloat16``).
  The in-bench batch-mode A/B (wave dispatch vs pipelined iteration vs
  sync-fetch iteration, ``SONATA_ITER_PIPELINE``) runs inside the
  default_policy config and reports the `iter_fetch_overlap` row.

Each configuration runs in its own subprocess (the toggles are read at
trace time; a warm jit cache would mask an in-process flip).

Usage::

    python tools/bench_cpu.py [--out BENCH_CPU_rNN.json]
                              [--streaming-out BENCH_STREAMING_CPU_rNN.json]

Writes two JSON artifacts, each entry tagged ``platform: "cpu"`` with the
exact env toggles used, plus cross-config ratios.
"""

from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import time
from pathlib import Path

REPO = Path(__file__).resolve().parent.parent

BATCH_CONFIGS = (
    ("baseline", {}),  # sub-pixel tconv, f32 (the defaults)
    ("naive_tconv", {"SONATA_TCONV": "naive"}),
    ("bf16", {"SONATA_COMPUTE_DTYPE": "bfloat16"}),
    ("int8", {"SONATA_DECODE_QUANT": "int8"}),  # weight-only decoder arm
)

# streaming arms: the policy A/Bs (r06 lineage) plus the ISSUE-11
# precision/fusion arms.  The in-bench batch-mode A/B (dispatch vs
# pipelined iteration vs sync-fetch iteration) runs inside the
# default_policy config; the precision arms skip it (--skip-ab) — their
# deliverable is the headline TTFB/throughput row vs default, each
# parity-gated by tests/test_decode_opts.py.
STREAMING_CONFIGS = (
    ("default_policy", {}),  # SONATA_DISPATCH_POLICY=auto
    ("coalescing_forced_on", {"SONATA_DISPATCH_POLICY": "on"}),
    ("coalescing_off", {"SONATA_DISPATCH_POLICY": "off"}),
    ("fused_epilogue_off", {"SONATA_FUSED_EPILOGUE": "off"}),
    ("int8_decoder", {"SONATA_DECODE_QUANT": "int8"}),
    ("bf16_decoder", {"SONATA_COMPUTE_DTYPE": "bfloat16"}),
)

#: configs whose bench_streaming run skips the in-bench A/B section
SKIP_AB_CONFIGS = ("fused_epilogue_off", "int8_decoder", "bf16_decoder")


def run_bench(script: str, env_extra: dict, timeout_s: float = 3600,
              script_args: tuple = ()):
    env = dict(os.environ)
    env.update(env_extra)
    env["JAX_PLATFORMS"] = "cpu"
    env.setdefault("SONATA_BENCH_ITERS", "2")  # CPU: keep wall time sane
    t0 = time.time()
    proc = subprocess.run(
        [sys.executable, str(REPO / script), *script_args], cwd=REPO,
        env=env, capture_output=True, text=True, timeout=timeout_s)
    wall = time.time() - t0
    lines = []
    for line in proc.stdout.splitlines():
        line = line.strip()
        if line.startswith("{"):
            try:
                lines.append(json.loads(line))
            except json.JSONDecodeError:
                pass
    return {"rc": proc.returncode, "wall_s": round(wall, 1),
            "results": lines,
            "stderr_tail": proc.stderr.strip().splitlines()[-3:]}


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--out", default="BENCH_CPU_r06.json")
    ap.add_argument("--streaming-out", default="BENCH_STREAMING_CPU_r06.json")
    ap.add_argument("--skip-streaming", action="store_true")
    ap.add_argument("--skip-batch", action="store_true")
    ap.add_argument("--streaming-configs", default=None,
                    help="comma-separated subset of the streaming config "
                         "names to run (default: all).  The in-bench "
                         "iteration-vs-dispatch A/B runs inside every "
                         "config, so a default_policy-only artifact "
                         "still carries the batch-mode comparison.")
    args = ap.parse_args()

    note = ("host-CPU regression numbers (absolute values are NOT "
            "comparable to the BASELINE.md TPU target — the ratios are "
            "the deliverable)")

    if not args.skip_batch:
        batch = {"platform": "cpu", "note": note,
                 "cpu_count": os.cpu_count(), "configs": {}}
        for name, env in BATCH_CONFIGS:
            print(f"[bench_cpu] batch config {name} ...", flush=True)
            batch["configs"][name] = {"env": env,
                                      **run_bench("bench.py", env)}

        def rtf(cfg):
            try:
                return batch["configs"][cfg]["results"][0]["value"]
            except (KeyError, IndexError, TypeError):
                return None

        base = rtf("baseline")
        # ratio > 1.0 ⇒ the baseline beats (is faster than) that config;
        # for naive_tconv that reads as "sub-pixel speedup"
        for cfg in ("naive_tconv", "bf16", "int8"):
            other = rtf(cfg)
            if base and other:
                batch[f"{cfg}_vs_baseline_rtf_ratio"] = round(other / base, 3)
        Path(args.out).write_text(json.dumps(batch, indent=1) + "\n")
        print(f"[bench_cpu] wrote {args.out}", flush=True)

    if args.skip_streaming:
        return
    streaming_configs = STREAMING_CONFIGS
    if args.streaming_configs:
        wanted = {w.strip() for w in args.streaming_configs.split(",")}
        streaming_configs = tuple(
            (n, e) for n, e in STREAMING_CONFIGS if n in wanted)
    streaming = {"platform": "cpu", "note": note,
                 "cpu_count": os.cpu_count(), "configs": {}}
    for name, env in streaming_configs:
        print(f"[bench_cpu] streaming config {name} ...", flush=True)
        extra = ("--skip-ab",) if name in SKIP_AB_CONFIGS else ()
        streaming["configs"][name] = {
            "env": env, **run_bench("bench_streaming.py", env,
                                    script_args=extra)}

    def metric(cfg, name):
        for r in streaming["configs"].get(cfg, {}).get("results", ()):
            if r.get("metric") == name:
                return r.get("value")
        return None

    # the acceptance ratios: default policy vs both forced shapes, at
    # every concurrency level plus aggregate throughput.  TTFB ratios
    # > 1.0 ⇒ the default beats (has lower TTFB than) the named config.
    for m in ("streaming_ttfb_p50",
              "streaming_ttfb_p50_at_4_streams",
              "streaming_ttfb_p50_at_8_streams"):
        d = metric("default_policy", m)
        for cfg in ("coalescing_forced_on", "coalescing_off"):
            o = metric(cfg, m)
            if d and o:
                streaming[f"{m}_default_vs_{cfg}"] = round(o / d, 3)
    d = metric("default_policy", "concurrent_streaming_audio_s_per_s")
    for cfg in ("coalescing_forced_on", "coalescing_off"):
        o = metric(cfg, "concurrent_streaming_audio_s_per_s")
        if d and o:
            # throughput: > 1.0 ⇒ the default delivers more audio-s/s
            streaming[f"throughput_default_vs_{cfg}"] = round(d / o, 3)
    # precision/fusion arms vs the default (fused-lax, f32): TTFB ratio
    # > 1.0 ⇒ the default is faster than the arm; throughput ratio
    # > 1.0 ⇒ the default delivers more audio-s/s.  On this 2-vCPU
    # host these carry the documented oversubscription noise — the
    # parity tests, not these rows, gate the arms' correctness.
    for cfg in SKIP_AB_CONFIGS:
        o = metric(cfg, "streaming_ttfb_p50")
        d1 = metric("default_policy", "streaming_ttfb_p50")
        if d1 and o:
            streaming[f"streaming_ttfb_p50_{cfg}_vs_default"] = \
                round(o / d1, 3)
        o = metric(cfg, "concurrent_streaming_audio_s_per_s")
        if d and o:
            streaming[f"throughput_default_vs_{cfg}"] = round(d / o, 3)
    Path(args.streaming_out).write_text(
        json.dumps(streaming, indent=1) + "\n")
    print(f"[bench_cpu] wrote {args.streaming_out}", flush=True)


if __name__ == "__main__":
    main()
