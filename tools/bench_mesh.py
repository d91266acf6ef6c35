#!/usr/bin/env python
"""Mesh bench: router-hop TTFB overhead vs direct, and kill-resilience.

Produces the committed ``MESH_rNN.json`` artifact (folded into
``BENCH_TREND.json`` by tools/bench_trend.py):

- **Hop overhead** — realtime-stream TTFB p50 through the sonata-mesh
  router vs. directly against one backend, at 1/4/8 concurrent streams
  (interleaved runs per arm, same backends, per the repo's A/B
  convention).  The router forwards stream chunks as raw bytes, so the
  hop should cost one loopback gRPC round-trip — the acceptance bar is
  ≤ 10% TTFB p50 at concurrency 1.  Per the r11/r12 convention on this
  2-vCPU host, TTFB ratios are *supporting* evidence; the deterministic
  counters below are the headline.
- **Kill resilience** (deterministic counters) — 8 concurrent streams
  through the router with a SIGKILL of one backend mid-run: the
  artifact records rerouted / dropped (must be 0) / mid-stream-typed
  counts straight from the router's own books.

Backends boot via ``tools/serving_smoke.py --mesh-node-boot`` (the same
pinned-port node boot the CI mesh phase uses), sharing one
``JAX_COMPILATION_CACHE_DIR`` so boots after the first are warm.

Run: ``JAX_PLATFORMS=cpu python tools/bench_mesh.py --out MESH_r01.json``
"""

from __future__ import annotations

import argparse
import json
import os
import signal
import statistics
import subprocess
import sys
import tempfile
import threading
import time
from pathlib import Path

os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ.setdefault("SONATA_WARMUP_LATTICE", "off")
sys.path.insert(0, str(Path(__file__).resolve().parent.parent))
sys.path.insert(0, str(Path(__file__).resolve().parent.parent / "tests"))
sys.path.insert(0, str(Path(__file__).resolve().parent))

SMOKE = Path(__file__).resolve().parent / "serving_smoke.py"

# the boot/readiness helpers are the smoke's (one copy of the
# node-boot recipe: bench backends ARE smoke mesh nodes)
from serving_smoke import free_port, wait_readyz  # noqa: E402

TEXT = ("A first sentence for the benchmark stream. "
        "A second sentence keeps it streaming.")
CONCURRENCIES = (1, 4, 8)
RUNS_PER_ARM = 3


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--out", default=None,
                    help="write the artifact here (e.g. MESH_r01.json); "
                         "omitted = print only")
    ap.add_argument("--runs", type=int, default=RUNS_PER_ARM)
    args = ap.parse_args()

    import jax

    jax.config.update("jax_platforms", "cpu")
    import grpc

    from sonata_tpu.frontends import grpc_messages as pb
    from sonata_tpu.frontends.mesh_server import create_mesh_server
    from voices import write_tiny_voice

    cfg = str(write_tiny_voice(Path(tempfile.mkdtemp(prefix="mesh_bench"))))
    cache = tempfile.mkdtemp(prefix="mesh_bench_cache")
    ports = [(free_port(), free_port()) for _ in range(2)]
    logs = [open(os.path.join(cache, f"node{i}.log"), "w")
            for i in range(2)]

    def boot(i: int) -> subprocess.Popen:
        env = dict(os.environ, JAX_PLATFORMS="cpu",
                   SMOKE_VOICE_CFG=cfg, JAX_COMPILATION_CACHE_DIR=cache,
                   MESH_NODE_GRPC_PORT=str(ports[i][0]),
                   MESH_NODE_METRICS_PORT=str(ports[i][1]))
        return subprocess.Popen(
            [sys.executable, str(SMOKE), "--mesh-node-boot"],
            env=env, stdout=logs[i], stderr=logs[i])

    def wait_ready(i: int, budget_s: float = 300.0) -> None:
        if not wait_readyz(ports[i][1], budget_s):
            raise RuntimeError(f"backend {i} never became ready")

    print("mesh-bench: booting 2 backend nodes...")
    procs = [boot(0), boot(1)]
    wait_ready(0)
    wait_ready(1)

    specs = [f"127.0.0.1:{g}/{m}" for g, m in ports]
    mesh_server, mesh_port = create_mesh_server(
        0, backends=specs, metrics_port=0, request_timeout_s=120.0)
    mesh_server.start()
    router = mesh_server.sonata_service.router
    print(f"mesh-bench: router on :{mesh_port} over {specs}")

    def realtime(port: int):
        channel = grpc.insecure_channel(f"127.0.0.1:{port}")
        return channel, channel.unary_stream(
            "/sonata_grpc.sonata_grpc/SynthesizeUtteranceRealtime",
            request_serializer=lambda m: m.encode(),
            response_deserializer=pb.WaveSamples.decode)

    direct_channel, direct_rpc = realtime(ports[0][0])
    mesh_channel, mesh_rpc = realtime(mesh_port)
    # learn the voice id from the backend (same config path everywhere)
    ch = grpc.insecure_channel(f"127.0.0.1:{ports[0][0]}")
    voices = ch.unary_unary(
        "/sonata_grpc.sonata_grpc/ListVoices",
        request_serializer=lambda m: m.encode(),
        response_deserializer=pb.VoiceList.decode)(pb.Empty())
    voice_id = voices.voices[0].voice_id
    ch.close()

    def stream_once(rpc, out: list, j: int) -> None:
        t0 = time.monotonic()
        ttfb = None
        err = None
        chunks = 0
        try:
            for chunk in rpc(pb.Utterance(voice_id=voice_id, text=TEXT),
                             timeout=120.0):
                if len(chunk.wav_samples) > 0:
                    if ttfb is None:
                        ttfb = time.monotonic() - t0
                    chunks += 1
        except grpc.RpcError as e:
            err = e
        out[j] = (ttfb, chunks, err)

    def wave(rpc, concurrency: int) -> list:
        out: list = [None] * concurrency
        threads = [threading.Thread(target=stream_once,
                                    args=(rpc, out, j))
                   for j in range(concurrency)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=300.0)
        return [r[0] for r in out if r and r[0] is not None
                and r[2] is None]

    # settle laps (both arms warm their channels + any residual state)
    wave(direct_rpc, 1)
    wave(mesh_rpc, 1)

    results = []
    overhead_by_c = {}
    for c in CONCURRENCIES:
        ttfbs = {"direct": [], "mesh": []}
        # c=1 is the acceptance row and its absolute TTFB (~17 ms warm)
        # sits within host scheduling jitter of the ~1-2 ms hop cost:
        # take 5x the samples so the p50 ratio measures the hop, not
        # one noisy wakeup
        runs = args.runs * 5 if c == 1 else args.runs
        for _run in range(runs):
            # interleaved arms: host noise hits both alike
            ttfbs["direct"].extend(wave(direct_rpc, c))
            ttfbs["mesh"].extend(wave(mesh_rpc, c))
        p50 = {arm: statistics.median(v) for arm, v in ttfbs.items()
               if v}
        if len(p50) < 2:
            raise RuntimeError(f"bench wave failed at concurrency {c}: "
                               f"{ {k: len(v) for k, v in ttfbs.items()} }")
        ratio = p50["mesh"] / p50["direct"]
        overhead_by_c[c] = ratio
        print(f"mesh-bench: c={c}: direct p50 "
              f"{p50['direct'] * 1e3:.1f} ms, mesh p50 "
              f"{p50['mesh'] * 1e3:.1f} ms, hop ratio {ratio:.3f}")
        results.extend([
            {"metric": f"ttfb_p50_direct_c{c}_ms",
             "value": round(p50["direct"] * 1e3, 2)},
            {"metric": f"ttfb_p50_mesh_c{c}_ms",
             "value": round(p50["mesh"] * 1e3, 2)},
            {"metric": f"mesh_hop_overhead_c{c}",
             "value": round(ratio, 4)},
        ])

    # ---- kill phase: deterministic reroute/membership counters ----
    stats0 = dict(router.stats)
    out: list = [None] * 8
    threads = [threading.Thread(target=stream_once,
                                args=(mesh_rpc, out, j))
               for j in range(8)]
    for t in threads:
        t.start()
    # kill INSIDE the dispatch window (warm TTFB at c=8 is ~70 ms on
    # this host): some streams must still be pre-first-chunk so the
    # reroute counter measures something
    time.sleep(0.04)
    procs[1].send_signal(signal.SIGKILL)
    for t in threads:
        t.join(timeout=300.0)
    completed = sum(1 for r in out if r and r[2] is None and r[1] > 0)
    dropped = sum(1 for r in out if r and r[2] is not None and r[1] == 0)
    midstream = sum(1 for r in out
                    if r and r[2] is not None and r[1] > 0)
    rerouted = router.stats["rerouted"] - stats0["rerouted"]
    print(f"mesh-bench: kill phase: {completed} completed, {rerouted} "
          f"rerouted, {dropped} dropped (must be 0), {midstream} "
          "mid-stream typed failures")
    results.extend([
        {"metric": "kill_completed_requests", "value": completed},
        {"metric": "kill_rerouted_requests", "value": rerouted},
        {"metric": "kill_dropped_requests", "value": dropped},
        {"metric": "kill_midstream_typed_failures", "value": midstream},
    ])

    mesh_channel.close()
    direct_channel.close()
    mesh_server.stop(grace=None)
    mesh_server.sonata_service.shutdown()
    for p in procs:
        if p.poll() is None:
            p.send_signal(signal.SIGTERM)
    for p in procs:
        try:
            p.wait(timeout=60.0)
        except subprocess.TimeoutExpired:
            p.kill()
    for f in logs:
        f.close()

    artifact = {
        "bench": "mesh",
        "host": "ci-cpu",
        "notes": (
            "sonata-mesh router-hop bench: 2 backend subprocesses "
            "(serving_smoke --mesh-node-boot, shared jax cache) + "
            "in-process router; realtime-stream TTFB p50, arms "
            "interleaved per run, %d runs per arm per concurrency.  "
            "Headline metrics are the DETERMINISTIC kill-phase "
            "counters (8 concurrent streams, SIGKILL of one backend "
            "mid-run: dropped must be 0 — not-yet-streaming requests "
            "reroute; mid-stream ones fail typed); per the r11/r12 "
            "noise convention on this 2-vCPU host the TTFB ratios are "
            "supporting evidence (acceptance: hop overhead <= 1.10 "
            "at concurrency 1).  NOTE the c4/c8 'overhead' ratios "
            "compare the 2-node mesh against ONE direct backend, so "
            "values < 1 are the fleet spreading load, not a free "
            "hop — only the c1 row isolates the hop cost." % args.runs),
        "configs": {"mesh": {"results": results}},
    }
    if args.out:
        Path(args.out).write_text(
            json.dumps(artifact, indent=1, sort_keys=True) + "\n",
            encoding="utf-8")
        print(f"mesh-bench: wrote {args.out}")
    ok = dropped == 0 and overhead_by_c.get(1, 99.0) <= 1.10
    print(f"mesh-bench: {'PASS' if ok else 'FAIL'} "
          f"(hop overhead c1 {overhead_by_c.get(1):.3f}, "
          f"dropped {dropped})")
    return 0 if ok else 1


if __name__ == "__main__":
    rc = main()
    sys.stdout.flush()
    os._exit(rc)
