#!/usr/bin/env python
"""Fleet-observability bench: scope-export scrape cost and the
node-side overhead bar, plus the fleet scoreboard snapshot.

Produces the committed ``FLEET_rNN.json`` artifact (folded into
``BENCH_TREND.json`` by tools/bench_trend.py):

- **Export overhead** (the acceptance bar, ≤ 1.02 — the PR-7 node-side
  scope budget): realtime-stream TTFB p50 *directly against one
  backend* with an external scraper hammering its
  ``/debug/scope/export`` at 2 Hz (2.5–10× the default fleet cadence,
  so the measurement is conservative) vs. the same backend unscraped,
  arms interleaved per run.  Per the r11/r12 convention on this 2-vCPU
  host, absolute TTFBs are noisy; the ratio of interleaved medians is
  the committed number.
- **Scrape cost** (deterministic): p50 wall time and payload size of a
  ``/debug/scope/export`` GET against the traffic-fed node — what each
  node pays per fleet cadence tick.
- **Fleet scoreboard**: the router's ``/debug/fleet`` after the
  traffic mix — nodes reporting, merged e2e quantile count, scrape
  counters — recorded so the artifact pins that aggregation actually
  populated during the run.

Backends boot via ``tools/serving_smoke.py --mesh-node-boot`` (the same
pinned-port node boot the CI mesh phase and bench_mesh use), sharing
one ``JAX_COMPILATION_CACHE_DIR`` so boots after the first are warm.

Run: ``JAX_PLATFORMS=cpu python tools/bench_fleet.py --out FLEET_r01.json``

``--cache-artifact`` (ISSUE 16) instead produces the committed
``FLEETCACHE_rNN.json``: a fleet of THREE cache-enabled backends behind
the router, driven by the same seeded Zipf(1.1) template workload the
single-node ``CACHE_rNN.json`` pins (16 templates, 80 draws, 4
concurrent clients), once with cache-affinity routing off (plain
least-outstanding spreads each template's first hit across the fleet —
the cold-miss dilution this PR exists to kill) and once with
``SONATA_FLEETCACHE=1``.  The fleet hit ratio is computed from the
summed per-node ``sonata_synth_cache_{hits,misses}_total`` deltas, so
router-side single-flight followers (admitted without touching a
backend) are reported separately rather than flattering the ratio.
Acceptance bar: the affinity arm's fleet ratio stays >= 0.9x the
single-node CACHE_r01 ratio (0.825 -> >= 0.7425) while the plain arm
dilutes below it.

Run: ``JAX_PLATFORMS=cpu python tools/bench_fleet.py --cache-artifact \\
--out FLEETCACHE_r01.json``

``--tenancy-artifact`` (ISSUE 17) instead produces the committed
``TENANCY_rNN.json``: two backends sharing one jax cache, one booted
with a ``SONATA_TENANTS`` table (quiet tenant weight 3 with headroom
quota; burst tenant weight 1 throttled to 0.02 qps / burst 1) and one
booted with the table unset (tenancy fully off — the pre-PR wire
path).  Each node runs 30 unmeasured warm laps — absorbing the
padding-bucket compiles a lattice-off boot leaves cold — and drains
the burst bucket's one initial token, then serves
interleaved rounds of a solo quiet lap and a busy quiet lap run
against a continuous 3-thread burst flood whose clients honor the
refusals' ``retry-after-s`` trailer capped at 0.25 s.  On the tenancy node the burst tenant is quota-limited (typed
RESOURCE_EXHAUSTED refusals, near-zero admitted load), so the quiet
tenant's TTFB p99 stays within 1.25x of its own solo baseline; on the
off node every burst request is admitted and the quiet p99 degrades.
Per the r11/r12 convention, each arm is ratioed against its own node's
interleaved solo baseline so host noise and node-to-node skew cancel.

Run: ``JAX_PLATFORMS=cpu python tools/bench_fleet.py \\
--tenancy-artifact --out TENANCY_r01.json``
"""

from __future__ import annotations

import argparse
import json
import os
import statistics
import subprocess
import sys
import tempfile
import threading
import time
from pathlib import Path

os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ.setdefault("SONATA_WARMUP_LATTICE", "off")
# a fast fleet cadence so the scoreboard populates inside the bench
os.environ.setdefault("SONATA_FLEET_SCRAPE_INTERVAL_S", "1")
sys.path.insert(0, str(Path(__file__).resolve().parent.parent))
sys.path.insert(0, str(Path(__file__).resolve().parent.parent / "tests"))
sys.path.insert(0, str(Path(__file__).resolve().parent))

SMOKE = Path(__file__).resolve().parent / "serving_smoke.py"

from serving_smoke import free_port, http_get, wait_readyz  # noqa: E402

TEXT = ("A first sentence for the benchmark stream. "
        "A second sentence keeps it streaming.")
RUNS_PER_ARM = 10
STREAMS_PER_RUN = 3
SCRAPER_PERIOD_S = 0.5


N_TEMPLATES = 16
N_DRAWS = 80
ZIPF_EXPONENT = 1.1
CACHE_CLIENTS = 4          # stays under the affinity skew guard (4)
SINGLE_NODE_RATIO = 0.825  # the committed CACHE_r01 zipf_hit_ratio
CACHE_BAR = round(0.9 * SINGLE_NODE_RATIO, 4)


def cache_main(args) -> int:
    """The ``--cache-artifact`` mode: fleet-of-3 Zipf hit ratio with
    cache-affinity routing off vs on (see module docstring)."""
    import queue
    import random

    import jax

    jax.config.update("jax_platforms", "cpu")
    import grpc

    from sonata_tpu.frontends import grpc_messages as pb
    from sonata_tpu.frontends.mesh_server import create_mesh_server
    from sonata_tpu.serving import parse_prometheus_text
    from voices import write_tiny_voice

    cfg = str(write_tiny_voice(
        Path(tempfile.mkdtemp(prefix="fleetcache_bench"))))
    cache = tempfile.mkdtemp(prefix="fleetcache_bench_cache")
    ports = [(free_port(), free_port()) for _ in range(3)]
    logs = [open(os.path.join(cache, f"node{i}.log"), "w")
            for i in range(3)]

    def boot(i: int) -> subprocess.Popen:
        env = dict(os.environ, JAX_PLATFORMS="cpu",
                   SMOKE_VOICE_CFG=cfg, JAX_COMPILATION_CACHE_DIR=cache,
                   SONATA_SYNTH_CACHE_MB="16",
                   MESH_NODE_GRPC_PORT=str(ports[i][0]),
                   MESH_NODE_METRICS_PORT=str(ports[i][1]))
        return subprocess.Popen(
            [sys.executable, str(SMOKE), "--mesh-node-boot"],
            env=env, stdout=logs[i], stderr=logs[i])

    print("fleet-bench[cache]: booting 3 cache-enabled backend nodes...")
    procs = [boot(i) for i in range(3)]
    for i in range(3):
        if not wait_readyz(ports[i][1], 300.0):
            raise RuntimeError(f"backend {i} never became ready")
    specs = [f"127.0.0.1:{g}/{m}" for g, m in ports]

    def fleet_counter(family: str) -> float:
        total = 0.0
        for _g, m in ports:
            parsed = parse_prometheus_text(
                http_get(f"http://127.0.0.1:{m}/metrics")[1])
            total += sum(v for _lbl, v in parsed.get(family, []))
        return total

    def run_arm(tag: str, affinity_on: bool) -> dict:
        """One arm: its own router (fleetcache on/off via env), the
        seeded Zipf draw sequence over tag-prefixed templates (distinct
        texts per arm so arms can never hit each other's entries), 4
        concurrent clients, hit ratio from node-counter deltas."""
        if affinity_on:
            os.environ["SONATA_FLEETCACHE"] = "1"
        try:
            server, port = create_mesh_server(
                0, backends=specs, metrics_port=0,
                request_timeout_s=120.0)
        finally:
            os.environ.pop("SONATA_FLEETCACHE", None)
        server.start()
        channel = grpc.insecure_channel(f"127.0.0.1:{port}")
        synth = channel.unary_stream(
            "/sonata_grpc.sonata_grpc/SynthesizeUtterance",
            request_serializer=lambda m: m.encode(),
            response_deserializer=pb.SynthesisResult.decode)
        load = channel.unary_unary(
            "/sonata_grpc.sonata_grpc/LoadVoice",
            request_serializer=lambda m: m.encode(),
            response_deserializer=pb.VoiceInfo.decode)
        # through the router: the affinity tier learns the voice's key
        # inputs from the wire (inert for voices it has not seen)
        voice_id = load(pb.VoicePath(config_path=cfg),
                        timeout=120.0).voice_id

        texts = [f"{tag}-arm fleet cache bench template {i} repeats."
                 for i in range(N_TEMPLATES)]
        weights = [1.0 / (rank + 1) ** ZIPF_EXPONENT
                   for rank in range(N_TEMPLATES)]
        rng = random.Random(args.seed)
        draws = rng.choices(range(N_TEMPLATES), weights=weights,
                            k=N_DRAWS)
        h0 = fleet_counter("sonata_synth_cache_hits_total")
        m0 = fleet_counter("sonata_synth_cache_misses_total")
        work: queue.Queue = queue.Queue()
        for idx in draws:
            work.put(idx)
        errors: list = []

        def client() -> None:
            while True:
                try:
                    idx = work.get_nowait()
                except queue.Empty:
                    return
                try:
                    results = list(synth(
                        pb.Utterance(voice_id=voice_id,
                                     text=texts[idx]),
                        timeout=120.0))
                    if not results or not results[0].wav_samples:
                        errors.append("empty")
                except grpc.RpcError as e:
                    errors.append(e.code().name)

        t0 = time.monotonic()
        threads = [threading.Thread(target=client)
                   for _ in range(CACHE_CLIENTS)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=600.0)
        wall = time.monotonic() - t0
        if errors:
            raise RuntimeError(f"{tag} arm saw errors: {errors[:5]}")
        hits = fleet_counter("sonata_synth_cache_hits_total") - h0
        misses = fleet_counter("sonata_synth_cache_misses_total") - m0
        fcs = server.sonata_service.fleetcache
        snap = dict(fcs.snapshot()["stats"]) if fcs is not None else {}
        channel.close()
        server.stop(grace=None)
        server.sonata_service.shutdown()
        ratio = hits / max(hits + misses, 1)
        print(f"fleet-bench[cache]: {tag} arm: {int(hits)} hits / "
              f"{int(misses)} misses over {N_DRAWS} draws "
              f"({len(set(draws))} unique templates) -> fleet ratio "
              f"{ratio:.4f} in {wall:.1f}s "
              f"(followers={snap.get('singleflight_follows', 0)}, "
              f"skew_fallbacks={snap.get('skew_fallbacks', 0)})")
        return {"ratio": round(ratio, 4), "hits": int(hits),
                "misses": int(misses),
                "unique_templates": len(set(draws)),
                "wall_s": round(wall, 2), "snap": snap}

    # plain arm first: the dilution baseline this PR kills
    off = run_arm("off", affinity_on=False)
    on = run_arm("on", affinity_on=True)

    for p in procs:
        if p.poll() is None:
            p.terminate()
    for p in procs:
        try:
            p.wait(timeout=60.0)
        except subprocess.TimeoutExpired:
            p.kill()
    for f in logs:
        f.close()

    results = [
        {"metric": "fleet_zipf_hit_ratio_affinity", "value": on["ratio"]},
        {"metric": "fleet_zipf_hit_ratio_plain", "value": off["ratio"]},
        {"metric": "fleet_zipf_misses_affinity", "value": on["misses"]},
        {"metric": "fleet_zipf_misses_plain", "value": off["misses"]},
        {"metric": "zipf_unique_templates",
         "value": on["unique_templates"]},
        {"metric": "affinity_picks",
         "value": int(on["snap"].get("affinity_hits", 0))},
        {"metric": "affinity_skew_fallbacks",
         "value": int(on["snap"].get("skew_fallbacks", 0))},
        {"metric": "singleflight_follower_joins",
         "value": int(on["snap"].get("singleflight_follows", 0))},
    ]
    artifact = {
        "bench": "fleetcache",
        "host": "ci-cpu",
        "notes": (
            "bench_fleet --cache-artifact (ISSUE 16): 3 cache-enabled "
            "backend subprocesses (SONATA_SYNTH_CACHE_MB=16, shared "
            "jax cache) behind the mesh router; the CACHE_r01 seeded "
            "Zipf workload (16 templates, rank^-1.1 weights, 80 draws, "
            "seed %d) over %d concurrent clients, once with plain "
            "least-outstanding routing and once with cache-affinity "
            "routing (SONATA_FLEETCACHE=1), distinct per-arm text "
            "prefixes so the arms share no cache entries.  Fleet hit "
            "ratio is summed per-node synth-cache counter deltas; "
            "router-side single-flight followers are reported "
            "separately (they are admissions served without touching "
            "a backend, so folding them in would flatter the ratio).  "
            "Acceptance: affinity arm >= %.4f (0.9x the single-node "
            "CACHE_r01 zipf_hit_ratio of %.3f) with the plain arm "
            "diluted below the affinity arm; hot-set replication is "
            "left at its default (off) so replica priming cannot "
            "pollute the measured counters."
            % (args.seed, CACHE_CLIENTS, CACHE_BAR, SINGLE_NODE_RATIO)),
        "configs": {"fleetcache": {"results": results}},
    }
    if args.out:
        Path(args.out).write_text(
            json.dumps(artifact, indent=1, sort_keys=True) + "\n",
            encoding="utf-8")
        print(f"fleet-bench[cache]: wrote {args.out}")
    ok = on["ratio"] >= CACHE_BAR and off["ratio"] < on["ratio"]
    print(f"fleet-bench[cache]: {'PASS' if ok else 'FAIL'} "
          f"(affinity {on['ratio']:.4f} >= {CACHE_BAR:.4f}, "
          f"plain {off['ratio']:.4f} diluted)")
    return 0 if ok else 1


TENANCY_ROUNDS = 4          # interleaved solo/busy rounds per node
TENANCY_QUIET_PER_ROUND = 4  # quiet streams per block
TENANCY_BURST_THREADS = 3    # continuous burst clients during busy laps
TENANCY_BAR = 1.25           # ISSUE-17 acceptance: on-arm p99 ratio
TENANCY_BACKOFF_CAP_S = 0.25  # bursters honor retry-after up to this
TENANCY_WARM_LAPS = 30      # unmeasured laps absorbing bucket compiles
TENANCY_TABLE = {"tenants": {
    "quiet": {"weight": 3, "qps": 200, "burst": 200},
    # 0.02 qps = one admitted burst request per 50 s: after the warm
    # lap drains the bucket's initial token, the measured windows see
    # the quota-enforced steady state (refusals, not synthesis)
    "burst": {"weight": 1, "qps": 0.02, "burst": 1}}}


def _p99(samples: list) -> float:
    ordered = sorted(samples)
    return ordered[min(len(ordered) - 1,
                       int(round(0.99 * (len(ordered) - 1))))]


def tenancy_main(args) -> int:
    """The ``--tenancy-artifact`` mode: quiet-tenant TTFB p99 under a
    noisy-neighbor burst, tenancy on vs off (see module docstring)."""
    import jax

    jax.config.update("jax_platforms", "cpu")
    import grpc

    from sonata_tpu.frontends import grpc_messages as pb
    from voices import write_tiny_voice

    cfg = str(write_tiny_voice(
        Path(tempfile.mkdtemp(prefix="tenancy_bench"))))
    cache = tempfile.mkdtemp(prefix="tenancy_bench_cache")
    ports = [(free_port(), free_port()) for _ in range(2)]
    logs = [open(os.path.join(cache, f"node{i}.log"), "w")
            for i in range(2)]

    def boot(i: int, tenants: str | None) -> subprocess.Popen:
        env = dict(os.environ, JAX_PLATFORMS="cpu",
                   SMOKE_VOICE_CFG=cfg, JAX_COMPILATION_CACHE_DIR=cache,
                   MESH_NODE_GRPC_PORT=str(ports[i][0]),
                   MESH_NODE_METRICS_PORT=str(ports[i][1]))
        env.pop("SONATA_TENANTS", None)
        # the laps reuse fixed texts (shape-stable: a varying counter
        # word can cross a padding bucket and drop a multi-second
        # compile into a measured window) — so the synthesis cache must
        # stay off or every measured lap would be a cache hit
        env.pop("SONATA_SYNTH_CACHE_MB", None)
        if tenants is not None:
            env["SONATA_TENANTS"] = tenants
        return subprocess.Popen(
            [sys.executable, str(SMOKE), "--mesh-node-boot"],
            env=env, stdout=logs[i], stderr=logs[i])

    print("fleet-bench[tenancy]: booting tenancy-on and tenancy-off "
          "backend nodes...")
    procs = [boot(0, json.dumps(TENANCY_TABLE)), boot(1, None)]
    for i in range(2):
        if not wait_readyz(ports[i][1], 300.0):
            raise RuntimeError(f"backend {i} never became ready")

    def run_arm(tag: str, grpc_port: int) -> dict:
        """One node's interleaved solo/busy quiet laps with a
        continuous burst-tenant load during the busy blocks."""
        channel = grpc.insecure_channel(f"127.0.0.1:{grpc_port}")
        synth = channel.unary_stream(
            "/sonata_grpc.sonata_grpc/SynthesizeUtterance",
            request_serializer=lambda m: m.encode(),
            response_deserializer=pb.SynthesisResult.decode)
        load = channel.unary_unary(
            "/sonata_grpc.sonata_grpc/LoadVoice",
            request_serializer=lambda m: m.encode(),
            response_deserializer=pb.VoiceInfo.decode)
        voice_id = load(pb.VoicePath(config_path=cfg),
                        timeout=120.0).voice_id

        def quiet_once() -> float:
            t0 = time.monotonic()
            for chunk in synth(
                    pb.Utterance(voice_id=voice_id,
                                 text=f"Quiet {tag} lap keeps "
                                      f"streaming along."),
                    timeout=120.0,
                    metadata=(("x-tenant-id", "quiet"),)):
                if len(chunk.wav_samples) > 0:
                    return time.monotonic() - t0
            raise RuntimeError("quiet stream produced no audio")

        stop_burst = threading.Event()
        stats = {"admitted": 0, "refused": 0, "errors": 0}
        stats_lock = threading.Lock()

        burst_text = f"Burst {tag} worker flood hammers the node."

        def burster(worker: int) -> None:
            while not stop_burst.is_set():
                backoff = TENANCY_BACKOFF_CAP_S
                try:
                    results = list(synth(
                        pb.Utterance(voice_id=voice_id,
                                     text=burst_text),
                        timeout=120.0,
                        metadata=(("x-tenant-id", "burst"),)))
                    with stats_lock:
                        if results and results[0].wav_samples:
                            stats["admitted"] += 1
                        else:
                            stats["errors"] += 1
                except grpc.RpcError as e:
                    refused = (e.code()
                               == grpc.StatusCode.RESOURCE_EXHAUSTED)
                    # a refusal must carry the retry-after-s trailer
                    # (the typed-refusal contract); honor it, capped so
                    # the flood stays continuous pressure
                    retry_after = None
                    for k, v in (e.trailing_metadata() or ()):
                        if k == "retry-after-s":
                            retry_after = float(v)
                    with stats_lock:
                        if refused and retry_after is not None:
                            stats["refused"] += 1
                        else:
                            stats["errors"] += 1
                    if retry_after is not None:
                        backoff = min(retry_after,
                                      TENANCY_BACKOFF_CAP_S)
                    stop_burst.wait(backoff)

        # warm block: these nodes boot with the warmup lattice off, so
        # the first lap compiles the text's bucket — and the per-request
        # PRNG seed sequence deterministically pushes one later lap's
        # sampled durations into the NEIGHBOR frame bucket (~lap 25,
        # one more multi-second compile).  30 unmeasured laps absorb
        # both so the measured windows compare warm steady states.
        for _ in range(TENANCY_WARM_LAPS):
            quiet_once()
        # warm lap AS the burst tenant: compiles the burst text's
        # padding bucket and drains the bucket's initial token, so the
        # measured windows compare steady states — quota-limited
        # refusals (on arm) vs an unthrottled flood (off arm) — not
        # one-time compile/token cost
        list(synth(pb.Utterance(voice_id=voice_id, text=burst_text),
                   timeout=120.0,
                   metadata=(("x-tenant-id", "burst"),)))
        solo, busy = [], []
        for _round in range(TENANCY_ROUNDS):
            for _ in range(TENANCY_QUIET_PER_ROUND):
                solo.append(quiet_once())
            stop_burst.clear()
            threads = [threading.Thread(target=burster, args=(w,),
                                        daemon=True)
                       for w in range(TENANCY_BURST_THREADS)]
            for t in threads:
                t.start()
            try:
                for _ in range(TENANCY_QUIET_PER_ROUND):
                    busy.append(quiet_once())
            finally:
                stop_burst.set()
                for t in threads:
                    t.join(timeout=120.0)
        channel.close()
        print(f"fleet-bench[tenancy]: {tag} solo ms "
              f"{[round(s * 1e3, 1) for s in solo]}")
        print(f"fleet-bench[tenancy]: {tag} busy ms "
              f"{[round(s * 1e3, 1) for s in busy]}")
        out = {"solo_p50": statistics.median(solo),
               "solo_p99": _p99(solo),
               "busy_p50": statistics.median(busy),
               "busy_p99": _p99(busy), **stats}
        out["ratio_p99"] = out["busy_p99"] / out["solo_p99"]
        print(f"fleet-bench[tenancy]: {tag} arm: quiet p99 "
              f"{out['solo_p99'] * 1e3:.0f} ms solo -> "
              f"{out['busy_p99'] * 1e3:.0f} ms busy (ratio "
              f"{out['ratio_p99']:.3f}); burst admitted="
              f"{stats['admitted']} refused={stats['refused']} "
              f"errors={stats['errors']}")
        return out

    on = run_arm("on", ports[0][0])
    off = run_arm("off", ports[1][0])

    for p in procs:
        if p.poll() is None:
            p.terminate()
    for p in procs:
        try:
            p.wait(timeout=60.0)
        except subprocess.TimeoutExpired:
            p.kill()
    for f in logs:
        f.close()

    results = [
        {"metric": "quiet_ttfb_p99_ratio_tenancy_on",
         "value": round(on["ratio_p99"], 4)},
        {"metric": "quiet_ttfb_p99_ratio_tenancy_off",
         "value": round(off["ratio_p99"], 4)},
        {"metric": "quiet_ttfb_p99_solo_on_ms",
         "value": round(on["solo_p99"] * 1e3, 2)},
        {"metric": "quiet_ttfb_p99_busy_on_ms",
         "value": round(on["busy_p99"] * 1e3, 2)},
        {"metric": "quiet_ttfb_p99_solo_off_ms",
         "value": round(off["solo_p99"] * 1e3, 2)},
        {"metric": "quiet_ttfb_p99_busy_off_ms",
         "value": round(off["busy_p99"] * 1e3, 2)},
        {"metric": "burst_quota_refusals_on",
         "value": int(on["refused"])},
        {"metric": "burst_admitted_on", "value": int(on["admitted"])},
        {"metric": "burst_quota_refusals_off",
         "value": int(off["refused"])},
        {"metric": "burst_admitted_off",
         "value": int(off["admitted"])},
    ]
    artifact = {
        "bench": "tenancy",
        "host": "ci-cpu",
        "notes": (
            "bench_fleet --tenancy-artifact (ISSUE 17): two backend "
            "subprocesses sharing one jax cache, node 0 booted with a "
            "SONATA_TENANTS table (quiet: weight 3 / qps 200; burst: "
            "weight 1 / qps 0.02 / burst 1) and node 1 booted with "
            "the table unset (tenancy off, the pre-PR wire path).  "
            "Each arm runs %d unmeasured warm laps (absorbing the "
            "padding-bucket compiles a lattice-off boot leaves cold) "
            "and drains the burst bucket's initial token (one "
            "admitted burst synthesis outside the measured windows), "
            "then runs %d interleaved "
            "rounds of %d solo quiet streams followed by %d quiet "
            "streams against a continuous %d-thread burst-tenant "
            "flood whose clients honor the retry-after-s trailer "
            "capped at %.2f s, and is ratioed against its own node's "
            "solo TTFB p99 so host noise and node skew cancel.  "
            "Acceptance: the tenancy-on quiet p99 ratio stays <= %.2f "
            "because the burst tenant is quota-limited at admission "
            "(typed RESOURCE_EXHAUSTED with retry-after-s, near-zero "
            "admitted load), with refusals_on >= 1 and refusals_off "
            "== 0 pinning that only the tenancy node throttles; the "
            "off arm's ratio must exceed the on arm's (the "
            "noisy-neighbor degradation this PR exists to bound).  "
            "Per the r11/r12 convention on this 2-vCPU host, absolute "
            "TTFB rows are supporting evidence only."
            % (TENANCY_WARM_LAPS, TENANCY_ROUNDS,
               TENANCY_QUIET_PER_ROUND, TENANCY_QUIET_PER_ROUND,
               TENANCY_BURST_THREADS, TENANCY_BACKOFF_CAP_S,
               TENANCY_BAR)),
        "configs": {"tenancy": {"results": results}},
    }
    if args.out:
        Path(args.out).write_text(
            json.dumps(artifact, indent=1, sort_keys=True) + "\n",
            encoding="utf-8")
        print(f"fleet-bench[tenancy]: wrote {args.out}")
    ok = (on["ratio_p99"] <= TENANCY_BAR
          and on["refused"] >= 1
          and off["refused"] == 0
          and off["ratio_p99"] > on["ratio_p99"])
    print(f"fleet-bench[tenancy]: {'PASS' if ok else 'FAIL'} "
          f"(on-arm p99 ratio {on['ratio_p99']:.4f} <= {TENANCY_BAR}, "
          f"off-arm {off['ratio_p99']:.4f} degraded, "
          f"{on['refused']} quota refusals on / {off['refused']} off)")
    return 0 if ok else 1


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--out", default=None,
                    help="write the artifact here (e.g. FLEET_r01.json);"
                         " omitted = print only")
    ap.add_argument("--runs", type=int, default=RUNS_PER_ARM)
    ap.add_argument("--cache-artifact", action="store_true",
                    help="produce FLEETCACHE_rNN.json instead: fleet-"
                         "of-3 Zipf hit ratio, affinity off vs on")
    ap.add_argument("--seed", type=int, default=1234,
                    help="Zipf draw seed for --cache-artifact")
    ap.add_argument("--tenancy-artifact", action="store_true",
                    help="produce TENANCY_rNN.json instead: quiet-"
                         "tenant TTFB p99 under a noisy-neighbor "
                         "burst, tenancy on vs off")
    args = ap.parse_args()

    if args.cache_artifact:
        return cache_main(args)
    if args.tenancy_artifact:
        return tenancy_main(args)

    import jax

    jax.config.update("jax_platforms", "cpu")
    import grpc

    from sonata_tpu.frontends import grpc_messages as pb
    from sonata_tpu.frontends.mesh_server import create_mesh_server
    from voices import write_tiny_voice

    cfg = str(write_tiny_voice(Path(tempfile.mkdtemp(prefix="fleet_bench"))))
    cache = tempfile.mkdtemp(prefix="fleet_bench_cache")
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

    print("fleet-bench: booting 2 backend nodes...")
    procs = [boot(0), boot(1)]
    for i in range(2):
        if not wait_readyz(ports[i][1], 300.0):
            raise RuntimeError(f"backend {i} never became ready")

    specs = [f"127.0.0.1:{g}/{m}" for g, m in ports]
    mesh_server, mesh_port = create_mesh_server(
        0, backends=specs, metrics_port=0, request_timeout_s=120.0)
    mesh_server.start()
    mesh_base = \
        f"http://127.0.0.1:{mesh_server.sonata_runtime.http_port}"
    node0_base = f"http://127.0.0.1:{ports[0][1]}"
    print(f"fleet-bench: router on :{mesh_port} over {specs}")

    def realtime(port: int):
        channel = grpc.insecure_channel(f"127.0.0.1:{port}")
        return channel, channel.unary_stream(
            "/sonata_grpc.sonata_grpc/SynthesizeUtteranceRealtime",
            request_serializer=lambda m: m.encode(),
            response_deserializer=pb.WaveSamples.decode)

    direct_channel, direct_rpc = realtime(ports[0][0])
    mesh_channel, mesh_rpc = realtime(mesh_port)
    ch = grpc.insecure_channel(f"127.0.0.1:{ports[0][0]}")
    voices = ch.unary_unary(
        "/sonata_grpc.sonata_grpc/ListVoices",
        request_serializer=lambda m: m.encode(),
        response_deserializer=pb.VoiceList.decode)(pb.Empty())
    voice_id = voices.voices[0].voice_id
    ch.close()

    def stream_once(rpc) -> float:
        t0 = time.monotonic()
        for chunk in rpc(pb.Utterance(voice_id=voice_id, text=TEXT),
                         timeout=120.0):
            if len(chunk.wav_samples) > 0:
                return time.monotonic() - t0
        raise RuntimeError("stream produced no audio")

    # traffic through the router so the fleet plane has data to merge
    for _ in range(6):
        stream_once(mesh_rpc)

    # ---- export overhead A/B (direct to node 0, scraper on/off) ----
    stop_scraper = threading.Event()

    def scraper() -> None:
        while not stop_scraper.wait(SCRAPER_PERIOD_S):
            try:
                http_get(node0_base + "/debug/scope/export")
            except Exception:
                pass

    stream_once(direct_rpc)  # settle lap
    ttfbs = {"baseline": [], "scraped": []}
    for _run in range(args.runs):
        # interleaved arms: host noise hits both alike
        for _ in range(STREAMS_PER_RUN):
            ttfbs["baseline"].append(stream_once(direct_rpc))
        stop_scraper.clear()
        t = threading.Thread(target=scraper, daemon=True)
        t.start()
        try:
            for _ in range(STREAMS_PER_RUN):
                ttfbs["scraped"].append(stream_once(direct_rpc))
        finally:
            stop_scraper.set()
            t.join(timeout=5.0)
    p50 = {arm: statistics.median(v) for arm, v in ttfbs.items()}
    overhead = p50["scraped"] / p50["baseline"]
    print(f"fleet-bench: TTFB p50 baseline {p50['baseline'] * 1e3:.1f} "
          f"ms, export-scraped {p50['scraped'] * 1e3:.1f} ms, "
          f"overhead ratio {overhead:.4f}")

    # ---- scrape cost (deterministic) ----
    costs, size = [], 0
    for _ in range(20):
        t0 = time.monotonic()
        code, body = http_get(node0_base + "/debug/scope/export")
        costs.append(time.monotonic() - t0)
        assert code == 200, f"export answered {code}"
        size = len(body)
    scrape_p50_ms = statistics.median(costs) * 1e3
    print(f"fleet-bench: /debug/scope/export p50 {scrape_p50_ms:.2f} ms, "
          f"{size} bytes")

    # ---- fleet scoreboard ----
    fdoc = {}
    deadline = time.monotonic() + 20.0
    while time.monotonic() < deadline:
        code, body = http_get(mesh_base + "/debug/fleet")
        fdoc = json.loads(body) if code == 200 else {}
        if fdoc.get("fleet", {}).get("nodes_reporting") == 2:
            break
        time.sleep(0.5)
    fleet = fdoc.get("fleet", {})
    e2e_5m = fleet.get("stage_quantiles", {}).get("e2e", {}).get("5m", {})
    print(f"fleet-bench: scoreboard: {fleet.get('nodes_reporting')} "
          f"reporting, e2e 5m count {e2e_5m.get('count')}, "
          f"p99 {e2e_5m.get('p99')}")

    results = [
        {"metric": "export_overhead_ratio", "value": round(overhead, 4)},
        {"metric": "ttfb_p50_baseline_ms",
         "value": round(p50["baseline"] * 1e3, 2)},
        {"metric": "ttfb_p50_export_scraped_ms",
         "value": round(p50["scraped"] * 1e3, 2)},
        {"metric": "scrape_export_p50_ms",
         "value": round(scrape_p50_ms, 3)},
        {"metric": "scrape_export_bytes", "value": size},
        {"metric": "fleet_nodes_reporting",
         "value": fleet.get("nodes_reporting", 0)},
        {"metric": "fleet_e2e_count_5m",
         "value": e2e_5m.get("count", 0)},
    ]
    if isinstance(e2e_5m.get("p99"), (int, float)):
        results.append({"metric": "fleet_e2e_p99_5m_s",
                        "value": round(e2e_5m["p99"], 4)})

    mesh_channel.close()
    direct_channel.close()
    mesh_server.stop(grace=None)
    mesh_server.sonata_service.shutdown()
    for p in procs:
        if p.poll() is None:
            p.terminate()
    for p in procs:
        try:
            p.wait(timeout=60.0)
        except subprocess.TimeoutExpired:
            p.kill()
    for f in logs:
        f.close()

    artifact = {
        "bench": "fleet",
        "host": "ci-cpu",
        "notes": (
            "sonata-fleetscope bench: 2 backend subprocesses "
            "(serving_smoke --mesh-node-boot, shared jax cache) + "
            "in-process router with a 1 s fleet scrape cadence.  "
            "export_overhead_ratio is the ISSUE-13 acceptance bar "
            "(<= 1.02, the PR-7 node-side scope budget): realtime TTFB "
            "p50 direct against node 0 with an external 2 Hz "
            "/debug/scope/export scraper vs unscraped, %d interleaved "
            "runs x %d streams per arm — the scraper runs at 2.5-10x "
            "the default 5 s fleet cadence, so the committed ratio is "
            "conservative.  scrape_export_* rows are the deterministic "
            "per-tick cost each node pays; the fleet_* rows pin that "
            "the router's /debug/fleet scoreboard actually populated "
            "from both nodes during the run.  Per the r11/r12 noise "
            "convention on this 2-vCPU host, absolute TTFB rows are "
            "supporting evidence only." % (args.runs, STREAMS_PER_RUN)),
        "configs": {"fleet": {"results": results}},
    }
    if args.out:
        Path(args.out).write_text(
            json.dumps(artifact, indent=1, sort_keys=True) + "\n",
            encoding="utf-8")
        print(f"fleet-bench: wrote {args.out}")
    ok = (overhead <= 1.02
          and fleet.get("nodes_reporting") == 2
          and e2e_5m.get("count", 0) >= 1)
    print(f"fleet-bench: {'PASS' if ok else 'FAIL'} "
          f"(export overhead {overhead:.4f} <= 1.02, "
          f"{fleet.get('nodes_reporting')} nodes reporting)")
    return 0 if ok else 1


if __name__ == "__main__":
    rc = main()
    sys.stdout.flush()
    os._exit(rc)
