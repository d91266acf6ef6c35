#!/usr/bin/env python
"""CI serving smoke: boot the gRPC server with a fake voice, probe the
metrics/health plane, and assert the serving-runtime contract end to end.

Checks (exit 0 only if all hold):

1. server boots with an ephemeral gRPC port and metrics HTTP port;
2. ``/healthz`` is 200 from the start, ``/readyz`` is 503 before warmup;
3. LoadVoice over the real wire + one-utterance warmup flips ``/readyz``
   to 200 (the rolling-restart readiness gate);
4. ``/metrics`` serves Prometheus text that the strict parser accepts,
   including queue-depth, shed, TTFB-histogram, and queue-wait series;
5. ``CheckHealth`` over gRPC agrees with the HTTP plane;
6. request-scoped tracing: a synthesis request carrying an
   ``x-request-id`` yields a complete span tree (admission → phonemize →
   queue-wait → dispatch → stream-emit) at ``/debug/traces``, the shared
   dispatch span carries batch/bucket/padding/compile attribution,
   ``/debug/traces?format=chrome`` is valid Chrome trace-event JSON, and
   ``/debug/slowest`` stays bounded;
7. a second server boot with ``replicas=2`` on the 2 forced host
   devices: per-replica gauges appear in ``/metrics``, readiness
   survives one breaker-open replica (flipping only at zero healthy),
   and the traced request's dispatch span is attributed to a replica
   and device;
8. warm-restart check (ISSUE 9): two boots with the bucket-lattice
   warmup (``SONATA_WARMUP_LATTICE=minimal``) against one populated
   ``JAX_COMPILATION_CACHE_DIR`` — the second boot's time-to-ready must be
   materially faster (the persistent compile cache carries the
   executables), ``sonata_runtime_cold_compiles_total`` must stay 0
   under the smoke's traffic mix on both boots, and
   ``sonata_warmup_progress`` must read 1.0.  With
   ``--warmup-artifact PATH`` the cold/warm numbers are written as a
   bench-trend-foldable artifact (the committed ``WARMUP_rNN.json``).

Run: ``JAX_PLATFORMS=cpu python tools/serving_smoke.py`` (used by
tools/run_ci_local.sh and .github/workflows/ci.yml).
"""

from __future__ import annotations

import os
import sys
import tempfile
import urllib.error
import urllib.request
from pathlib import Path

os.environ.setdefault("JAX_PLATFORMS", "cpu")
# phases 1-7 predate the lattice warmup and pin their own timings; the
# warm-restart phase opts back in explicitly
os.environ.setdefault("SONATA_WARMUP_LATTICE", "off")
# small slowest-ring so the boundedness check exercises eviction (must be
# set before sonata_tpu imports create the default tracer)
os.environ.setdefault("SONATA_TRACE_SLOWEST", "4")
# the replica-pool phase needs >= 2 devices; force a 2-device CPU host
# unless the caller already forced a count (idempotent under conftest)
if "xla_force_host_platform_device_count" not in os.environ.get(
        "XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                               + " --xla_force_host_platform_device_count=2"
                               ).strip()
sys.path.insert(0, str(Path(__file__).resolve().parent.parent))
sys.path.insert(0, str(Path(__file__).resolve().parent.parent / "tests"))


def http_get(url: str) -> tuple[int, str]:
    try:
        with urllib.request.urlopen(url, timeout=10) as resp:
            return resp.getcode(), resp.read().decode()
    except urllib.error.HTTPError as e:
        return e.code, e.read().decode()


def free_port() -> int:
    """An OS-assigned free loopback port (mesh nodes need PINNED ports
    so a restarted backend rejoins at the same address; also reused by
    tools/bench_mesh.py)."""
    import socket

    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    p = s.getsockname()[1]
    s.close()
    return p


def wait_readyz(metrics_port: int, budget_s: float = 300.0) -> bool:
    """Poll a node's /readyz until 200 (shared with bench_mesh)."""
    import time

    deadline = time.monotonic() + budget_s
    url = f"http://127.0.0.1:{metrics_port}/readyz"
    while time.monotonic() < deadline:
        try:
            if http_get(url)[0] == 200:
                return True
        except Exception:
            pass
        time.sleep(0.25)
    return False


def warm_restart_boot() -> int:
    """Subprocess entry for the warm-restart phase: one full server
    boot — voice load, calibration + bucket-lattice warmup, the smoke
    traffic mix — reporting one ``WARMBOOT {json}`` line.  The cache
    dir, lattice mode, and voice config arrive via the parent's env
    (``JAX_COMPILATION_CACHE_DIR`` / ``SONATA_WARMUP_LATTICE`` /
    ``SMOKE_VOICE_CFG``); the persistent compile cache is configured
    BEFORE the first compile, like a real process boot."""
    import jax

    jax.config.update("jax_platforms", "cpu")
    from sonata_tpu.utils.jax_cache import enable_persistent_compile_cache

    cache_dir = enable_persistent_compile_cache(0.0)
    import json
    import time

    import grpc

    from sonata_tpu.frontends import grpc_messages as pb
    from sonata_tpu.frontends.grpc_server import create_server
    from sonata_tpu.serving import parse_prometheus_text

    cfg = os.environ["SMOKE_VOICE_CFG"]
    server, port = create_server(0, continuous_batching=True,
                                 metrics_port=0, request_timeout_s=60.0)
    server.start()
    runtime = server.sonata_runtime
    base = f"http://127.0.0.1:{runtime.http_port}"
    channel = grpc.insecure_channel(f"127.0.0.1:{port}")
    load = channel.unary_unary(
        "/sonata_grpc.sonata_grpc/LoadVoice",
        request_serializer=lambda m: m.encode(),
        response_deserializer=pb.VoiceInfo.decode)
    synthesize = channel.unary_stream(
        "/sonata_grpc.sonata_grpc/SynthesizeUtterance",
        request_serializer=lambda m: m.encode(),
        response_deserializer=pb.SynthesisResult.decode)
    t0 = time.monotonic()
    info = load(pb.VoicePath(config_path=cfg))
    server.sonata_service.warmup_and_mark_ready()
    time_to_ready_s = time.monotonic() - t0
    ready_code, _ = http_get(base + "/readyz")
    # the traffic mix: single-sentence texts across several text
    # buckets, two passes so pass 2 runs on a traffic-fed estimator
    mix = ("Warm restart check.", "Short.",
           "A medium sentence for the middle text bucket.",
           "A considerably longer sentence that should land well into "
           "one of the larger text buckets of the warmup lattice.")
    for _pass in range(2):
        for text in mix:
            results = list(synthesize(pb.Utterance(
                voice_id=info.voice_id, text=text)))
            assert results and len(results[0].wav_samples) > 0
    parsed = parse_prometheus_text(http_get(base + "/metrics")[1])
    colds = sum(v for _lbl, v in parsed.get(
        "sonata_runtime_cold_compiles_total", []))
    progress = parsed.get("sonata_warmup_progress", [({}, 0.0)])[0][1]
    report = {"ready": ready_code == 200,
              "time_to_ready_s": round(time_to_ready_s, 3),
              "progress": progress,
              "runtime_cold_compiles": int(colds),
              "lattice_shapes":
                  runtime.warmup_progress.snapshot()["total"],
              "cache_dir": cache_dir}
    print("WARMBOOT " + json.dumps(report))
    server.stop(grace=None)
    server.sonata_service.shutdown()
    return 0


def mesh_node_boot() -> int:
    """Subprocess entry for the mesh phase (ISSUE 12): one backend
    sonata node on pinned ports (``MESH_NODE_GRPC_PORT`` /
    ``MESH_NODE_METRICS_PORT`` — pinned so a restarted node rejoins the
    router's membership at the same address), voice loaded + warmed,
    SIGTERM handlers installed (the drain path IS the phase's subject),
    reporting one ``MESHNODE {json}`` line and then serving until
    signalled.

    ``MESH_NODE_EMPTY=1`` (ISSUE 14) boots the node with NO voices —
    ready immediately, empty ``voices=`` line on ``/readyz`` — the
    restarted-after-SIGKILL shape whose voice set the router's
    placement reconciler must restore with zero operator action."""
    import jax

    jax.config.update("jax_platforms", "cpu")
    from sonata_tpu.utils.jax_cache import enable_persistent_compile_cache

    enable_persistent_compile_cache(0.0)
    import json

    import grpc

    from sonata_tpu.frontends import grpc_messages as pb
    from sonata_tpu.frontends.grpc_server import (
        create_server,
        install_signal_handlers,
    )

    cfg = os.environ["SMOKE_VOICE_CFG"]
    grpc_port = int(os.environ["MESH_NODE_GRPC_PORT"])
    metrics_port = int(os.environ["MESH_NODE_METRICS_PORT"])
    server, port = create_server(grpc_port, continuous_batching=True,
                                 metrics_port=metrics_port,
                                 request_timeout_s=60.0)
    server.start()
    install_signal_handlers(server)
    voice_id = ""
    if os.environ.get("MESH_NODE_EMPTY") == "1":
        runtime = server.sonata_runtime
        runtime.warmup_progress.finish()
        runtime.health.set_ready("no preloaded voices")
    else:
        channel = grpc.insecure_channel(f"127.0.0.1:{port}")
        load = channel.unary_unary(
            "/sonata_grpc.sonata_grpc/LoadVoice",
            request_serializer=lambda m: m.encode(),
            response_deserializer=pb.VoiceInfo.decode)
        info = load(pb.VoicePath(config_path=cfg))
        voice_id = info.voice_id
        server.sonata_service.warmup_and_mark_ready()
    print("MESHNODE " + json.dumps(
        {"voice_id": voice_id, "grpc_port": port,
         "metrics_port": metrics_port,
         "node_id": server.sonata_runtime.node_id}), flush=True)
    server.wait_for_termination()
    return 0


def iteration_boot() -> int:
    """Subprocess entry for the iteration-mode phase (PR 10): one full
    server boot with ``SONATA_BATCH_MODE=iteration`` + the full warmup
    lattice (which now enumerates the iteration-mode window-decoder
    ladder), concurrent realtime streams as traffic, reporting one
    ``ITERBOOT {json}`` line: readiness, per-iteration attribution
    (dispatch spans with ``mode=iteration`` + peers, scope bucket rows),
    and the cold-compile count — which must be ZERO, proving the
    graduated-ladder iterations are recompile-free under the smoke mix.
    """
    import jax

    jax.config.update("jax_platforms", "cpu")
    from sonata_tpu.utils.jax_cache import enable_persistent_compile_cache

    enable_persistent_compile_cache(0.0)
    import json
    import threading

    import grpc

    from sonata_tpu.frontends import grpc_messages as pb
    from sonata_tpu.frontends.grpc_server import create_server
    from sonata_tpu.serving import parse_prometheus_text

    cfg = os.environ["SMOKE_VOICE_CFG"]
    server, port = create_server(0, continuous_batching=True,
                                 metrics_port=0, request_timeout_s=60.0)
    server.start()
    runtime = server.sonata_runtime
    base = f"http://127.0.0.1:{runtime.http_port}"
    channel = grpc.insecure_channel(f"127.0.0.1:{port}")
    load = channel.unary_unary(
        "/sonata_grpc.sonata_grpc/LoadVoice",
        request_serializer=lambda m: m.encode(),
        response_deserializer=pb.VoiceInfo.decode)
    realtime = channel.unary_stream(
        "/sonata_grpc.sonata_grpc/SynthesizeUtteranceRealtime",
        request_serializer=lambda m: m.encode(),
        response_deserializer=pb.WaveSamples.decode)
    info = load(pb.VoicePath(config_path=cfg))
    server.sonata_service.warmup_and_mark_ready()
    ready_code, _ = http_get(base + "/readyz")

    text = "Iteration mode serves concurrent streams from one batch."
    stream_ok = [False] * 4

    def run_stream(i: int) -> None:
        chunks = list(realtime(
            pb.Utterance(voice_id=info.voice_id, text=text),
            metadata=(("x-request-id", f"iter-smoke-{i}"),)))
        stream_ok[i] = bool(chunks) and all(
            len(c.wav_samples) > 0 for c in chunks)

    for _wave in range(2):
        threads = [threading.Thread(target=run_stream, args=(i,))
                   for i in range(4)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()

    # per-iteration attribution: the stream's trace carries dispatch
    # spans with mode=iteration, peer request ids, and padding ratio
    code, body = http_get(base + "/debug/traces")
    traces = json.loads(body).get("traces", []) if code == 200 else []
    it_spans = [s for t in traces for s in t.get("spans", [])
                if s["name"] == "dispatch"
                and s.get("attrs", {}).get("mode") == "iteration"]
    attributed = bool(it_spans) and all(
        {"batch_bucket", "padding_ratio", "request_ids",
         "dispatch_id"} <= set(s.get("attrs", {})) for s in it_spans)
    shared = any(len(s["attrs"].get("request_ids", [])) > 1
                 for s in it_spans)
    code, body = http_get(base + "/debug/buckets")
    bdoc = json.loads(body) if code == 200 else {}
    iter_rows = [r for r in bdoc.get("buckets", [])
                 if r.get("text_bucket") == 0]
    parsed = parse_prometheus_text(http_get(base + "/metrics")[1])
    colds = sum(v for _lbl, v in parsed.get(
        "sonata_runtime_cold_compiles_total", []))
    stats = server.sonata_service._voices[
        info.voice_id].synth.dispatch_stats() or {}
    report = {"ready": ready_code == 200,
              "streams_ok": all(stream_ok),
              "runtime_cold_compiles": int(colds),
              "iteration_spans": len(it_spans),
              "spans_attributed": attributed,
              "spans_share_iterations": shared,
              "bucket_rows_iteration": len(iter_rows),
              "batch_mode": stats.get("batch_mode"),
              "iteration_stats": stats.get("iteration")}
    print("ITERBOOT " + json.dumps(report))
    server.stop(grace=None)
    server.sonata_service.shutdown()
    return 0


def main(args=None) -> int:
    import jax

    jax.config.update("jax_platforms", "cpu")

    import grpc

    from sonata_tpu.frontends import grpc_messages as pb
    from sonata_tpu.frontends.grpc_server import create_server
    from sonata_tpu.serving import parse_prometheus_text
    from voices import write_tiny_voice

    cfg = str(write_tiny_voice(Path(tempfile.mkdtemp(prefix="smoke_voice"))))
    server, port = create_server(0, continuous_batching=True,
                                 metrics_port=0, request_timeout_s=60.0)
    server.start()
    runtime = server.sonata_runtime
    base = f"http://127.0.0.1:{runtime.http_port}"
    print(f"smoke: grpc on :{port}, metrics on {base}")

    failures: list[str] = []

    def check(name: str, ok: bool, detail: str = "") -> None:
        print(f"smoke: {'PASS' if ok else 'FAIL'} {name} {detail}")
        if not ok:
            failures.append(name)

    code, _ = http_get(base + "/healthz")
    check("healthz live at boot", code == 200, f"(code {code})")
    code, body = http_get(base + "/readyz")
    check("readyz 503 before warmup", code == 503, f"(code {code})")

    channel = grpc.insecure_channel(f"127.0.0.1:{port}")

    def unary(name, req, resp_cls):
        return channel.unary_unary(
            f"/sonata_grpc.sonata_grpc/{name}",
            request_serializer=lambda m: m.encode(),
            response_deserializer=resp_cls.decode)(req)

    info = unary("LoadVoice", pb.VoicePath(config_path=cfg), pb.VoiceInfo)
    check("LoadVoice over wire", bool(info.voice_id))
    h = unary("CheckHealth", pb.Empty(), pb.HealthStatus)
    check("CheckHealth not ready pre-warmup", h.live and not h.ready,
          f"({h.reason})")

    server.sonata_service.warmup_and_mark_ready()
    code, body = http_get(base + "/readyz")
    check("readyz flips 200 after warmup", code == 200, f"(code {code})")
    h = unary("CheckHealth", pb.Empty(), pb.HealthStatus)
    check("CheckHealth ready post-warmup", h.live and h.ready,
          f"({h.reason})")

    # one real synthesis so latency histograms and per-voice series move;
    # the explicit x-request-id makes its trace findable at /debug/traces
    synthesize = channel.unary_stream(
        "/sonata_grpc.sonata_grpc/SynthesizeUtterance",
        request_serializer=lambda m: m.encode(),
        response_deserializer=pb.SynthesisResult.decode)
    results = list(synthesize(
        pb.Utterance(voice_id=info.voice_id, text="Smoke test sentence."),
        metadata=(("x-request-id", "smoke-trace-1"),)))
    check("SynthesizeUtterance streams audio",
          len(results) >= 1 and len(results[0].wav_samples) > 0)

    # ---- request-scoped tracing (serving/tracing.py) ----
    code, body = http_get(base + "/debug/traces")
    check("/debug/traces is 200", code == 200)
    import json

    traces = json.loads(body).get("traces", [])
    trace = next((t for t in traces
                  if t["request_id"] == "smoke-trace-1"), None)
    check("trace found by client-sent x-request-id", trace is not None)
    if trace is not None:
        names = {s["name"] for s in trace["spans"]}
        check("complete span tree admission→stream-emit",
              {"SynthesizeUtterance", "admission", "phonemize",
               "queue-wait", "dispatch", "stream-emit"} <= names,
              f"({sorted(names)})")
        ids = {s["span_id"] for s in trace["spans"]}
        check("span parent links resolve within the trace",
              all(s["parent_id"] in ids for s in trace["spans"]
                  if s["parent_id"] is not None))
        dispatch = next(s for s in trace["spans"]
                        if s["name"] == "dispatch")
        attrs = dispatch.get("attrs", {})
        check("dispatch span carries coalescing attribution",
              all(k in attrs for k in ("dispatch_id", "batch_size",
                                       "request_ids", "batch_bucket",
                                       "padding_ratio", "compile")),
              f"({sorted(attrs)})")
        check("trace finished ok with a duration",
              trace["status"] == "ok" and trace["duration_ms"] > 0)
    code, body = http_get(base + "/debug/traces?format=chrome")
    try:
        chrome = json.loads(body)
        events = chrome["traceEvents"]
        ok = (isinstance(events, list)
              and any(e.get("ph") == "X" and "ts" in e and "dur" in e
                      for e in events))
    except (ValueError, KeyError):
        ok = False
    check("chrome trace-event export is valid JSON", ok)
    # boundedness: a burst of requests must not grow /debug/slowest past
    # its configured ring (SONATA_TRACE_SLOWEST=4 above)
    for i in range(6):
        list(synthesize(pb.Utterance(voice_id=info.voice_id,
                                     text=f"Bounded ring {i}.")))
    code, body = http_get(base + "/debug/slowest")
    slowest = json.loads(body).get("traces", [])
    check("/debug/slowest is bounded", code == 200 and len(slowest) <= 4,
          f"({len(slowest)} traces)")
    durs = [t["duration_ms"] for t in slowest]
    check("/debug/slowest is sorted slowest-first",
          durs == sorted(durs, reverse=True))

    code, text = http_get(base + "/metrics")
    check("/metrics is 200", code == 200)
    try:
        parsed = parse_prometheus_text(text)
    except ValueError as e:
        parsed = {}
        check("exposition format parses", False, f"({e})")
    else:
        check("exposition format parses", True,
              f"({len(parsed)} series names)")
    for required in ("sonata_ready", "sonata_in_flight",
                     "sonata_shed_total", "sonata_requests_total",
                     "sonata_ttfb_seconds_bucket",
                     "sonata_scheduler_queue_depth",
                     "sonata_queue_wait_seconds_bucket"):
        check(f"series {required}", required in parsed)
    qw_count = sum(v for _l, v in
                   parsed.get("sonata_queue_wait_seconds_count", []))
    check("queue-wait histogram observed the requests", qw_count >= 1)
    ttfb_total = sum(v for _labels, v in
                     parsed.get("sonata_ttfb_seconds_count", []))
    check("ttfb histogram observed the request", ttfb_total >= 1)

    # ---- scope aggregation plane (serving/scope.py) ----
    quant = parsed.get("sonata_stage_quantile", [])
    check("sonata_stage_quantile series populated", bool(quant),
          f"({len(quant)} series)")
    stages_seen = {lbl.get("stage") for lbl, _v in quant}
    check("quantiles cover the e2e stage", "e2e" in stages_seen,
          f"({sorted(stages_seen)})")
    burn = parsed.get("sonata_slo_burn_rate", [])
    check("sonata_slo_burn_rate series populated", bool(burn),
          f"({len(burn)} series)")
    check("burn windows are 5m and 1h",
          {lbl.get("window") for lbl, _v in burn} == {"5m", "1h"})
    check("sonata_slo_budget_remaining series populated",
          bool(parsed.get("sonata_slo_budget_remaining")))
    check("sonata_dispatch_padding_waste_seconds_total labeled by voice",
          any(lbl.get("voice") == info.voice_id for lbl, _v in
              parsed.get("sonata_dispatch_padding_waste_seconds_total",
                         [])))
    code, body = http_get(base + "/debug/quantiles")
    check("/debug/quantiles is 200", code == 200)
    qdoc = json.loads(body)
    check("/debug/quantiles has e2e data",
          qdoc.get("stages", {}).get("e2e", {}).get("1m", {})
              .get("count", 0) >= 1)
    check("/debug/quantiles reports the SLO table",
          {s["name"] for s in qdoc.get("slos", [])} >= {"error_rate"})
    code, body = http_get(base + "/debug/buckets")
    check("/debug/buckets is 200 with dispatches", code == 200
          and json.loads(body)["dispatches_total"] >= 1)
    code, body = http_get(base + "/debug/timeline")
    tdoc = json.loads(body) if code == 200 else {}
    check("/debug/timeline is populated",
          code == 200 and tdoc.get("count", 0) >= 1,
          f"({tdoc.get('count', 0)} snapshots)")
    snaps = tdoc.get("snapshots") or [{}]
    check("timeline snapshots carry recorder fields",
          all(k in snaps[-1] for k in ("ts", "dispatches_total",
                                       "degradation_level", "in_flight")),
          f"({sorted(snaps[-1])})")

    server.stop(grace=None)
    server.sonata_service.shutdown()

    # ---- replica-pool phase: fresh server over the 2 forced devices ----
    import jax

    # long probe interval: the half-open prober would otherwise restore a
    # force-opened replica mid-smoke and race the zero-healthy check
    os.environ["SONATA_REPLICA_PROBE_INTERVAL_S"] = "600"
    n_dev = len(jax.local_devices())
    check("host has >= 2 devices for the replica phase", n_dev >= 2,
          f"({n_dev} devices)")
    server, port = create_server(0, replicas=2, metrics_port=0,
                                 request_timeout_s=60.0)
    server.start()
    runtime = server.sonata_runtime
    base = f"http://127.0.0.1:{runtime.http_port}"
    channel = grpc.insecure_channel(f"127.0.0.1:{port}")
    info = unary("LoadVoice", pb.VoicePath(config_path=cfg), pb.VoiceInfo)
    v = server.sonata_service._voices[info.voice_id]
    check("voice runs a 2-replica pool",
          v.pool is not None and len(v.pool.replicas) == 2)
    server.sonata_service.warmup_and_mark_ready()
    code, _ = http_get(base + "/readyz")
    check("readyz 200 with pool warmed", code == 200, f"(code {code})")
    check("warmup dispatched on every replica",
          all(r.dispatches > 0 for r in v.pool.replicas),
          str([r.snapshot() for r in v.pool.replicas]))
    code, text = http_get(base + "/metrics")
    try:
        parsed = parse_prometheus_text(text)
    except ValueError as e:
        parsed = {}
        check("replica exposition parses", False, f"({e})")
    else:
        check("replica exposition parses", True)
    for required in ("sonata_replica_dispatches",
                     "sonata_replica_breaker_state",
                     "sonata_replica_outstanding", "sonata_replica_device",
                     "sonata_pool_routed", "sonata_pool_healthy_replicas"):
        series = parsed.get(required, [])
        check(f"series {required}", bool(series),
              f"({len(series)} series)")
    replica_labels = {lbl.get("replica")
                      for lbl, _v in parsed.get(
                          "sonata_replica_dispatches", [])}
    check("per-replica series for both replicas",
          replica_labels == {"0", "1"}, f"({replica_labels})")

    # one breaker-open replica must degrade capacity, not readiness
    v.pool.force_open(0, "smoke")
    code, _ = http_get(base + "/readyz")
    check("readyz survives one breaker-open replica", code == 200,
          f"(code {code})")
    parsed_now = parse_prometheus_text(http_get(base + "/metrics")[1])
    healthy = [val for _lbl, val in
               parsed_now.get("sonata_pool_healthy_replicas", [])]
    check("healthy-replica gauge dropped to 1", healthy == [1.0],
          f"({healthy})")
    results = list(channel.unary_stream(
        "/sonata_grpc.sonata_grpc/SynthesizeUtterance",
        request_serializer=lambda m: m.encode(),
        response_deserializer=pb.SynthesisResult.decode)(
        pb.Utterance(voice_id=info.voice_id,
                     text="Still serving on one replica."),
        metadata=(("x-request-id", "smoke-replica-trace"),)))
    check("synthesis survives a broken replica",
          len(results) >= 1 and len(results[0].wav_samples) > 0)
    # the pool-served request's dispatch span must say WHICH chip served
    # it — the per-request attribution aggregate gauges cannot give
    code, body = http_get(base + "/debug/traces")
    traces = json.loads(body).get("traces", [])
    rt_trace = next((t for t in traces
                     if t["request_id"] == "smoke-replica-trace"), None)
    check("replica-phase trace found", rt_trace is not None)
    if rt_trace is not None:
        dspans = [s for s in rt_trace["spans"] if s["name"] == "dispatch"]
        check("dispatch span attributed to replica 1 and its device",
              any(s.get("attrs", {}).get("replica") == 1
                  and "device" in s.get("attrs", {}) for s in dspans),
              f"({[s.get('attrs') for s in dspans]})")
    # zero healthy replicas is the line readiness must not survive
    v.pool.force_open(1, "smoke")
    code, _ = http_get(base + "/readyz")
    check("readyz 503 at zero healthy replicas", code == 503,
          f"(code {code})")

    server.stop(grace=None)
    server.sonata_service.shutdown()

    # ---- synthesis-cache phase (ISSUE 15): content-addressed replay ----
    # A fresh server with a deliberately tiny byte budget (~10 KB) so
    # the over-budget workload below actually evicts.  The contract:
    # a repeat request replays bit-identical bytes AND chunk
    # boundaries, hits stamp a cache-hit span and produce ZERO new
    # dispatch spans, the hit/miss/bytes series populate, hit-ratio
    # rows ride /debug/quantiles, and eviction is LRU-first.
    import json

    os.environ["SONATA_SYNTH_CACHE_MB"] = "0.01"
    try:
        server, port = create_server(0, metrics_port=0,
                                     request_timeout_s=60.0)
    finally:
        del os.environ["SONATA_SYNTH_CACHE_MB"]
    server.start()
    runtime = server.sonata_runtime
    base = f"http://127.0.0.1:{runtime.http_port}"
    channel = grpc.insecure_channel(f"127.0.0.1:{port}")
    check("cache: runtime constructed the synth cache",
          runtime.synth_cache is not None)
    info = unary("LoadVoice", pb.VoicePath(config_path=cfg), pb.VoiceInfo)
    server.sonata_service.warmup_and_mark_ready()
    code, _ = http_get(base + "/readyz")
    check("cache: readyz 200 after warmup", code == 200, f"(code {code})")
    realtime = channel.unary_stream(
        "/sonata_grpc.sonata_grpc/SynthesizeUtteranceRealtime",
        request_serializer=lambda m: m.encode(),
        response_deserializer=pb.WaveSamples.decode)
    synthesize = channel.unary_stream(
        "/sonata_grpc.sonata_grpc/SynthesizeUtterance",
        request_serializer=lambda m: m.encode(),
        response_deserializer=pb.SynthesisResult.decode)

    def cache_metrics() -> dict:
        parsed = parse_prometheus_text(http_get(base + "/metrics")[1])
        return {name[len("sonata_synth_cache_"):]: sum(
                    v for _l, v in parsed.get(name, []))
                for name in ("sonata_synth_cache_hits_total",
                             "sonata_synth_cache_misses_total",
                             "sonata_synth_cache_inserts_total",
                             "sonata_synth_cache_evictions_total",
                             "sonata_synth_cache_bytes")}

    def dispatches_total() -> int:
        code, body = http_get(base + "/debug/buckets")
        # loud, not a sentinel: -1 == -1 would make the zero-dispatch
        # check below pass vacuously on a broken debug endpoint
        assert code == 200, f"/debug/buckets answered {code}"
        return json.loads(body)["dispatches_total"]

    cache_req = pb.Utterance(voice_id=info.voice_id,
                             text="Cache this exact stream.")
    miss_chunks = [c.wav_samples for c in realtime(
        cache_req, metadata=(("x-request-id", "cache-miss-1"),))]
    d_after_miss = dispatches_total()
    hit_chunks = [c.wav_samples for c in realtime(
        cache_req, metadata=(("x-request-id", "cache-hit-1"),))]
    check("cache: hit replays bit-identical bytes and chunk boundaries",
          bool(miss_chunks) and hit_chunks == miss_chunks,
          f"({len(miss_chunks)} vs {len(hit_chunks)} chunks)")
    check("cache: hit produced zero new device dispatches",
          dispatches_total() == d_after_miss,
          f"({d_after_miss} -> {dispatches_total()})")
    code, body = http_get(base + "/debug/traces")
    traces = json.loads(body).get("traces", []) if code == 200 else []
    t_hit = next((t for t in traces
                  if t["request_id"] == "cache-hit-1"), None)
    hit_names = {s["name"] for s in (t_hit or {}).get("spans", [])}
    check("cache: hit trace stamps a cache-hit span",
          t_hit is not None and "cache-hit" in hit_names,
          f"({sorted(hit_names)})")
    check("cache: hit trace carries zero dispatch spans",
          t_hit is not None and "dispatch" not in hit_names
          and "phonemize" not in hit_names)
    # utterance mode: repeat request, bit-identical WAV bytes
    utt_req = pb.Utterance(voice_id=info.voice_id,
                           text="Utterance replay. Second sentence.")
    utt_miss = [(r.wav_samples, r.rtf) for r in synthesize(utt_req)]
    utt_hit = [(r.wav_samples, r.rtf) for r in synthesize(utt_req)]
    check("cache: utterance hit is bit-identical WAV bytes hit-vs-miss",
          len(utt_miss) == 2 and utt_hit == utt_miss)
    m = cache_metrics()
    check("cache: hit/miss/insert/bytes metrics populated",
          m["hits_total"] >= 2 and m["misses_total"] >= 2
          and m["inserts_total"] >= 2 and m["bytes"] > 0, f"({m})")
    code, body = http_get(base + "/debug/quantiles")
    qdoc = json.loads(body) if code == 200 else {}
    crows = qdoc.get("synth_cache") or {}
    check("cache: hit-ratio rows on the scope plane",
          crows.get("hit_ratio") is not None
          and crows.get("bytes", 0) > 0, f"({crows})")
    # over-budget workload: distinct texts past the ~10 KB budget must
    # evict LRU-first — the oldest entry misses again, the newest hits
    evict_reqs = [pb.Utterance(voice_id=info.voice_id,
                               text=f"Evict workload sentence {i}.")
                  for i in range(8)]
    for r in evict_reqs:
        list(realtime(r))
    m = cache_metrics()
    check("cache: over-budget workload evicted entries",
          m["evictions_total"] >= 1
          and m["bytes"] <= 0.01 * 1024 * 1024, f"({m})")
    before = cache_metrics()
    list(realtime(evict_reqs[0]))   # the oldest: evicted ⇒ a miss
    mid = cache_metrics()
    list(realtime(evict_reqs[-1]))  # the newest: resident ⇒ a hit
    after = cache_metrics()
    check("cache: eviction is LRU-first (oldest misses, newest hits)",
          mid["misses_total"] == before["misses_total"] + 1
          and after["hits_total"] == mid["hits_total"] + 1,
          f"({before} -> {mid} -> {after})")

    server.stop(grace=None)
    server.sonata_service.shutdown()

    # ---- iteration-mode phase (PR 10): continuous batching ----
    # A real SUBPROCESS boot (the mode + full-lattice env must be set
    # before the process's first compile) with SONATA_BATCH_MODE=
    # iteration: concurrent realtime streams must ride shared
    # iterations with per-iteration attribution, and the full lattice
    # (which enumerates the graduated window-decoder ladder) must leave
    # ZERO post-warmup cold compiles under the smoke mix — the PR-9
    # containment proving the loop recompile-free.
    import json
    import subprocess
    import time

    iter_cache = tempfile.mkdtemp(prefix="smoke_iter_cache")
    # SONATA_ITER_PIPELINE=1 pinned explicitly (it is the default): the
    # smoke's attribution/books/cold-compile checks below must hold with
    # the dispatch and finish phases on different threads
    iter_env = dict(os.environ,
                    SONATA_BATCH_MODE="iteration",
                    SONATA_ITER_PIPELINE="1",
                    SONATA_DISPATCH_POLICY="on",
                    SONATA_WARMUP_LATTICE="full",
                    JAX_COMPILATION_CACHE_DIR=iter_cache,
                    JAX_PLATFORMS="cpu",
                    SMOKE_VOICE_CFG=cfg)
    p = subprocess.run(
        [sys.executable, __file__, "--iteration-boot"],
        env=iter_env, capture_output=True, text=True, timeout=900)
    check("iteration: boot subprocess exits 0", p.returncode == 0,
          f"(rc {p.returncode}: "
          f"{p.stderr.strip().splitlines()[-3:] if p.stderr else ''})")
    lines = [line for line in p.stdout.splitlines()
             if line.startswith("ITERBOOT ")]
    rep = json.loads(lines[-1][len("ITERBOOT "):]) if lines else {}
    check("iteration: readyz 200 after full-lattice warmup",
          rep.get("ready") is True, f"({rep})")
    check("iteration: batch mode resolved to iteration",
          rep.get("batch_mode") == "iteration")
    check("iteration: concurrent realtime streams all produced audio",
          rep.get("streams_ok") is True)
    check("iteration: dispatch spans carry per-iteration attribution",
          rep.get("spans_attributed") is True,
          f"({rep.get('iteration_spans')} spans)")
    it_stats_early = rep.get("iteration_stats") or {}
    check("iteration: streams shared iterations (peer request ids "
          "or rows > dispatches)",
          rep.get("spans_share_iterations") is True
          or it_stats_early.get("dispatches", 0)
          < it_stats_early.get("requests", 0))
    check("iteration: scope bucket rows account per-iteration padding",
          rep.get("bucket_rows_iteration", 0) >= 1)
    it_stats = rep.get("iteration_stats") or {}
    check("iteration: loop stats joined/retired balance",
          it_stats.get("joined", 0) >= 8
          and it_stats.get("retired") == it_stats.get("joined"),
          f"({it_stats})")
    check("iteration: sonata_runtime_cold_compiles_total == 0 "
          "(recompile-free under the smoke mix)",
          rep.get("runtime_cold_compiles") == 0,
          f"({rep.get('runtime_cold_compiles')})")

    # ---- warm-restart phase (ISSUE 9): lattice + persistent cache ----
    # Each boot is a real SUBPROCESS: a rolling restart is a new
    # process, and the JAX persistent compile cache only engages when
    # configured before the process's first compile (configuring it
    # mid-process after earlier phases compiled is silently inert).
    # Boot 1 runs against an initially-EMPTY JAX_COMPILATION_CACHE_DIR
    # (genuinely cold, populates it); boot 2 warms from disk.
    import json
    import subprocess
    import time

    cache_dir = tempfile.mkdtemp(prefix="smoke_jax_cache")
    # workers pinned to 1: the A/B below isolates the CACHE effect
    # (XLA persistent cache + the AOT executable store, both rooted in
    # JAX_COMPILATION_CACHE_DIR) on time-to-ready, so both boots must share
    # one compile configuration — a wider cold boot would flatter the
    # ratio.  The warm boot deserializes AOT executables instead of
    # retracing, which is what makes the ratio robust on a noisy host.
    boot_env = dict(os.environ,
                    JAX_COMPILATION_CACHE_DIR=cache_dir,
                    SONATA_WARMUP_LATTICE="minimal",
                    SONATA_WARMUP_WORKERS="1",
                    JAX_PLATFORMS="cpu",
                    SMOKE_VOICE_CFG=cfg)

    def boot(tag: str) -> dict:
        t0 = time.monotonic()
        p = subprocess.run(
            [sys.executable, __file__, "--warm-restart-boot"],
            env=boot_env, capture_output=True, text=True, timeout=600)
        proc_s = time.monotonic() - t0
        check(f"warm-restart[{tag}]: boot subprocess exits 0",
              p.returncode == 0, f"(rc {p.returncode}: "
              f"{p.stderr.strip().splitlines()[-3:] if p.stderr else ''})")
        lines = [line for line in p.stdout.splitlines()
                 if line.startswith("WARMBOOT ")]
        report = json.loads(lines[-1][len("WARMBOOT "):]) if lines else {}
        report["proc_total_s"] = round(proc_s, 3)
        check(f"warm-restart[{tag}]: readyz 200 after lattice warmup",
              report.get("ready") is True, f"({report})")
        check(f"warm-restart[{tag}]: sonata_warmup_progress is 1.0",
              report.get("progress") == 1.0, f"({report.get('progress')})")
        check(f"warm-restart[{tag}]: sonata_runtime_cold_compiles_total "
              "stays 0 under the traffic mix",
              report.get("runtime_cold_compiles") == 0,
              f"({report.get('runtime_cold_compiles')})")
        return report

    if args is None:
        import argparse

        args = argparse.Namespace(warmup_artifact=None)
    cold = boot("cold")
    check("warm-restart: cold boot populated the persistent cache",
          bool(os.listdir(cache_dir)),
          f"({len(os.listdir(cache_dir))} entries)")
    warm = boot("warm")
    ttr_cold = cold.get("time_to_ready_s", 0.0)
    ttr_warm = warm.get("time_to_ready_s", 1e9)
    n_shapes = cold.get("lattice_shapes", 0)
    colds_cold = cold.get("runtime_cold_compiles", -1)
    colds_warm = warm.get("runtime_cold_compiles", -1)
    ratio = ttr_warm / max(ttr_cold, 1e-9)
    check("warm-restart: second boot time-to-ready materially faster "
          "(persistent compile cache)", ratio < 0.6,
          f"(cold {ttr_cold:.1f}s -> warm {ttr_warm:.1f}s, "
          f"ratio {ratio:.3f}, {n_shapes} lattice shapes)")
    if args.warmup_artifact:
        artifact = {
            "bench": "warm_restart",
            "host": "ci-cpu",
            "notes": ("serving_smoke warm-restart phase: two subprocess "
                      "boots, SONATA_WARMUP_LATTICE=minimal, "
                      "SONATA_WARMUP_WORKERS=1 (controlled A/B), one "
                      "shared initially-empty JAX_COMPILATION_CACHE_DIR "
                      "rooting both the XLA persistent cache and the "
                      "AOT executable store — the warm boot "
                      "deserializes executables instead of retracing; "
                      "traffic mix of 4 texts x 2 passes per boot; "
                      "time_to_ready = LoadVoice -> readiness"),
            "configs": {"warm_restart": {"results": [
                {"metric": "time_to_ready_cold_s",
                 "value": round(ttr_cold, 3)},
                {"metric": "time_to_ready_warm_s",
                 "value": round(ttr_warm, 3)},
                {"metric": "time_to_ready_warm_over_cold",
                 "value": round(ratio, 4)},
                {"metric": "lattice_shapes_warmed",
                 "value": n_shapes},
                {"metric": "runtime_cold_compiles",
                 "value": int(colds_cold + colds_warm)},
            ]}}}
        Path(args.warmup_artifact).write_text(
            json.dumps(artifact, indent=1, sort_keys=True) + "\n",
            encoding="utf-8")
        print(f"smoke: wrote {args.warmup_artifact}")

    # ---- mesh phase (ISSUE 12): 2 backend subprocesses + 1 router ----
    # The first subsystem whose unit of failure is a whole PROCESS: the
    # router must treat a draining node (SIGTERM), a dead node
    # (SIGKILL), and a restarted node (same address, new pid) as
    # routing events — zero not-yet-streaming requests lost, router
    # /readyz tracking the healthy-node count, rejoin with no router
    # restart.
    import signal
    import threading

    from sonata_tpu.frontends.mesh_server import create_mesh_server
    from sonata_tpu.serving.replicas import CLOSED as NODE_CLOSED
    from sonata_tpu.serving.replicas import OPEN as NODE_OPEN

    node_ports = [(free_port(), free_port()) for _ in range(2)]
    mesh_cache = tempfile.mkdtemp(prefix="smoke_mesh_cache")
    node_logs = [open(os.path.join(mesh_cache, f"node{i}.log"), "w")
                 for i in range(2)]

    def boot_node(i: int, empty: bool = False) -> subprocess.Popen:
        env = dict(os.environ, JAX_PLATFORMS="cpu",
                   SMOKE_VOICE_CFG=cfg,
                   JAX_COMPILATION_CACHE_DIR=mesh_cache,
                   MESH_NODE_GRPC_PORT=str(node_ports[i][0]),
                   MESH_NODE_METRICS_PORT=str(node_ports[i][1]),
                   MESH_NODE_EMPTY="1" if empty else "0")
        return subprocess.Popen(
            [sys.executable, __file__, "--mesh-node-boot"],
            env=env, stdout=node_logs[i], stderr=node_logs[i])

    def wait_node_ready(i: int, budget_s: float = 300.0) -> bool:
        return wait_readyz(node_ports[i][1], budget_s)

    def wait_exit(p: subprocess.Popen, budget_s: float) -> bool:
        try:
            p.wait(timeout=budget_s)
            return True
        except subprocess.TimeoutExpired:
            return False

    procs = [boot_node(0), boot_node(1)]
    check("mesh: backend node 0 boots ready", wait_node_ready(0))
    check("mesh: backend node 1 boots ready", wait_node_ready(1))

    specs = [f"127.0.0.1:{g}/{m}" for g, m in node_ports]
    # fleetscope (ISSUE 13): a 1 s scrape cadence so the fleet checks
    # below populate within the smoke's budget (read at router build)
    os.environ["SONATA_FLEET_SCRAPE_INTERVAL_S"] = "1"
    mesh_server_obj, mesh_port = create_mesh_server(
        0, backends=specs, metrics_port=0, request_timeout_s=60.0)
    mesh_server_obj.start()
    router = mesh_server_obj.sonata_service.router
    mesh_base = \
        f"http://127.0.0.1:{mesh_server_obj.sonata_runtime.http_port}"
    mesh_channel = grpc.insecure_channel(f"127.0.0.1:{mesh_port}")
    mesh_synth = mesh_channel.unary_stream(
        "/sonata_grpc.sonata_grpc/SynthesizeUtterance",
        request_serializer=lambda m: m.encode(),
        response_deserializer=pb.SynthesisResult.decode)
    mesh_realtime = mesh_channel.unary_stream(
        "/sonata_grpc.sonata_grpc/SynthesizeUtteranceRealtime",
        request_serializer=lambda m: m.encode(),
        response_deserializer=pb.WaveSamples.decode)
    voice_id = info.voice_id  # same config path ⇒ same id on every node
    code, _ = http_get(mesh_base + "/readyz")
    check("mesh: router readyz 200 with both nodes up", code == 200,
          f"(code {code})")

    # ---- placement (ISSUE 14): register desired state through the
    # router (idempotent on nodes that boot-loaded the same config) so
    # every voice op from here on is reconciled, not fire-and-forget
    mesh_load = mesh_channel.unary_unary(
        "/sonata_grpc.sonata_grpc/LoadVoice",
        request_serializer=lambda m: m.encode(),
        response_deserializer=pb.VoiceInfo.decode)
    minfo = mesh_load(pb.VoicePath(config_path=cfg), timeout=120.0)
    check("placement: router LoadVoice records desired state with the "
          "fleet voice id", minfo.voice_id == voice_id,
          f"({minfo.voice_id} vs {voice_id})")

    def placement_gauge(name: str) -> float:
        parsed = parse_prometheus_text(
            http_get(mesh_base + "/metrics")[1])
        return sum(v for lbl, v in parsed.get(name, [])
                   if lbl.get("voice") == voice_id)

    deadline = time.monotonic() + 30.0
    while time.monotonic() < deadline and \
            placement_gauge("sonata_placement_converged") < 2:
        time.sleep(0.2)
    check("placement: sonata_placement_desired covers both nodes",
          placement_gauge("sonata_placement_desired") == 2.0)
    check("placement: both nodes converged holders within the probe "
          "cadence", placement_gauge("sonata_placement_converged") == 2.0)

    # the standard traffic mix through the router
    mesh_mix = ("Mesh routing check.", "Short.",
                "A medium sentence for the middle text bucket.",
                "A considerably longer sentence that should land well "
                "into one of the larger text buckets over the mesh hop.")
    mix_ok, served_nodes = True, set()
    for _pass in range(2):
        for text in mesh_mix:
            call = mesh_synth(pb.Utterance(voice_id=voice_id, text=text),
                              timeout=60.0)
            results = list(call)
            mix_ok = mix_ok and bool(results) \
                and len(results[0].wav_samples) > 0
            trailers = dict(call.trailing_metadata() or ())
            served_nodes.add(trailers.get("x-sonata-node-id"))
    check("mesh: traffic mix streams through the router", mix_ok)
    check("mesh: responses name the serving node in trailing metadata",
          served_nodes and None not in served_nodes,
          f"({served_nodes})")

    # ---- fleetscope (ISSUE 13): fleet scoreboard, fleet metrics, and
    # one stitched cross-process trace ----
    expected_node_ids = {f"127.0.0.1:{g}" for g, _m in node_ports}
    fdoc: dict = {}
    deadline = time.monotonic() + 30.0
    while time.monotonic() < deadline:
        code, body = http_get(mesh_base + "/debug/fleet")
        fdoc = json.loads(body) if code == 200 else {}
        if fdoc.get("fleet", {}).get("nodes_reporting") == 2 and \
                fdoc["fleet"]["stage_quantiles"]["e2e"]["5m"][
                    "count"] >= 1:
            break
        time.sleep(0.5)
    check("fleet: /debug/fleet populated from both backend "
          "subprocesses",
          fdoc.get("fleet", {}).get("nodes_reporting") == 2,
          f"({fdoc.get('fleet', {}).get('nodes_reporting')} reporting)")
    check("fleet: merged stage quantiles carry the traffic mix",
          fdoc.get("fleet", {}).get("stage_quantiles", {})
              .get("e2e", {}).get("5m", {}).get("count", 0) >= 1)
    reporting_ids = {n.get("node_id") for n in fdoc.get("nodes", [])
                     if n.get("reporting")}
    check("fleet: scoreboard names both node ids",
          reporting_ids == expected_node_ids,
          f"({reporting_ids} vs {expected_node_ids})")
    reporting_rows = [n for n in fdoc.get("nodes", [])
                      if n.get("reporting")]
    check("fleet: scoreboard rows carry scrape staleness and burn",
          bool(reporting_rows)
          and all({"export_age_s", "burn", "delta_p99_5m"} <= set(n)
                  for n in reporting_rows))
    slo_rows = fdoc.get("fleet", {}).get("slo", [])
    check("fleet: SLO table present with fast/slow burn windows",
          bool(slo_rows)
          and all(set(s.get("burn_rate", {})) == {"5m", "1h"}
                  for s in slo_rows))
    parsed = parse_prometheus_text(http_get(mesh_base + "/metrics")[1])
    fq = parsed.get("sonata_fleet_stage_quantile", [])
    check("fleet: sonata_fleet_stage_quantile series in router "
          "/metrics after traffic",
          any(lbl.get("stage") == "e2e" for lbl, _v in fq),
          f"({len(fq)} series)")
    fb = parsed.get("sonata_fleet_slo_burn_rate", [])
    check("fleet: sonata_fleet_slo_burn_rate series in router /metrics",
          bool(fb) and {lbl.get("window") for lbl, _v in fb} <= \
          {"5m", "1h"}, f"({len(fb)} series)")
    ages = parsed.get("sonata_mesh_node_scrape_age_seconds", [])
    check("fleet: sonata_mesh_node_scrape_age_seconds labeled per "
          "node_id",
          {lbl.get("node_id") for lbl, _v in ages} == expected_node_ids,
          f"({[lbl for lbl, _v in ages]})")
    check("fleet: scrape ages are fresh (inside the 1 s cadence x 5)",
          ages and all(v < 5.0 for _lbl, v in ages),
          f"({[v for _lbl, v in ages]})")
    # one stitched trace: router spans + serving-node spans under one
    # request id, re-based onto the router's clock (the Perfetto bar)
    stitched_ok, stitch_doc = False, {}
    call = mesh_synth(pb.Utterance(voice_id=voice_id,
                                   text="Stitch this trace."),
                      timeout=60.0,
                      metadata=(("x-request-id", "mesh-stitch-1"),))
    list(call)
    deadline = time.monotonic() + 10.0
    while time.monotonic() < deadline and not stitched_ok:
        code, body = http_get(
            mesh_base + "/debug/traces/stitched?id=mesh-stitch-1")
        stitch_doc = json.loads(body) if code == 200 else {}
        stitched_ok = stitch_doc.get("stitched", {}).get(
            "node_spans", 0) > 0
        if not stitched_ok:
            time.sleep(0.5)
    xs = [e for e in stitch_doc.get("traceEvents", [])
          if e.get("ph") == "X"]
    router_names = {e["name"] for e in xs if e.get("pid") == 1}
    node_names = {e["name"] for e in xs if e.get("pid") == 2}
    check("fleet: stitched trace carries the router span tree",
          {"admission", "mesh-dispatch", "stream-emit"} <= router_names,
          f"({sorted(router_names)})")
    check("fleet: stitched trace splices the serving node's spans",
          {"dispatch", "stream-emit"} & node_names,
          f"({sorted(node_names)})")
    check("fleet: every stitched span shares the one request id",
          bool(xs) and all(e.get("args", {}).get("request_id")
                           == "mesh-stitch-1" for e in xs))
    check("fleet: stitched doc names the serving node",
          stitch_doc.get("stitched", {}).get("node")
          in expected_node_ids,
          f"({stitch_doc.get('stitched')})")

    stream_text = ("A first sentence for the in-flight stream. "
                   "A second sentence keeps it streaming. "
                   "A third sentence finishes it off.")

    def run_stream(out: dict, j: int) -> None:
        chunks, err = 0, None
        try:
            for chunk in mesh_realtime(
                    pb.Utterance(voice_id=voice_id, text=stream_text),
                    timeout=90.0):
                if len(chunk.wav_samples) > 0:
                    chunks += 1
        except grpc.RpcError as e:
            err = e
        out[j] = (chunks, err)

    # SIGTERM drain mid-stream: in-flight streams finish on the
    # draining node (its listener stays up), the router reroutes new
    # work, and /readyz stays 200 at one healthy node
    term_results: dict = {}
    threads = [threading.Thread(target=run_stream,
                                args=(term_results, j))
               for j in range(4)]
    for t in threads:
        t.start()
    deadline = time.monotonic() + 10.0
    while time.monotonic() < deadline and \
            sum(n.outstanding for n in router.nodes) == 0:
        time.sleep(0.01)
    procs[0].send_signal(signal.SIGTERM)
    for t in threads:
        t.join(timeout=120.0)
    check("mesh: zero dropped streams across a backend SIGTERM drain",
          all(j in term_results and term_results[j][1] is None
              and term_results[j][0] > 0 for j in range(4)),
          str({j: (r[1].code().name if r[1] else f"{r[0]} chunks")
               for j, r in term_results.items()}))
    check("mesh: drained backend exits", wait_exit(procs[0], 90.0))
    deadline = time.monotonic() + 30.0
    while time.monotonic() < deadline and router.routable_count() != 1:
        time.sleep(0.1)
    check("mesh: draining node evicted from membership",
          router.routable_count() == 1,
          f"({router.routable_count()} routable)")
    code, _ = http_get(mesh_base + "/readyz")
    check("mesh: router readyz stays 200 at one healthy node",
          code == 200, f"(code {code})")
    results = list(mesh_synth(pb.Utterance(voice_id=voice_id,
                                           text="Still serving."),
                              timeout=60.0))
    check("mesh: requests keep serving on the surviving node",
          bool(results) and len(results[0].wav_samples) > 0)

    # restart node 0 on the SAME address: membership rejoin must need
    # no router restart (probe success flips the breaker half-open,
    # the next request closes it)
    procs[0] = boot_node(0)
    check("mesh: restarted backend boots ready", wait_node_ready(0))
    deadline = time.monotonic() + 120.0
    while time.monotonic() < deadline and router.routable_count() != 2:
        time.sleep(0.2)
    check("mesh: recovered backend rejoins without a router restart",
          router.routable_count() == 2,
          f"({router.routable_count()} routable)")
    # complete the rejoin: the node is HALF_OPEN until a trial request
    # closes its breaker — run one so the kill phase below starts from
    # two fully-closed nodes (a half-open node serves only its single
    # trial at a time, by breaker discipline)
    results = list(mesh_synth(pb.Utterance(voice_id=voice_id,
                                           text="Rejoin trial."),
                              timeout=60.0))
    deadline = time.monotonic() + 15.0
    while time.monotonic() < deadline and \
            any(n.state != NODE_CLOSED for n in router.nodes):
        results = list(mesh_synth(pb.Utterance(voice_id=voice_id,
                                               text="Rejoin trial."),
                                  timeout=60.0))
        time.sleep(0.1)
    check("mesh: trial request closes the rejoined node's breaker",
          bool(results) and all(n.state == NODE_CLOSED for n in router.nodes),
          f"({[n.snapshot() for n in router.nodes]})")

    # SIGKILL under 8 concurrent streams (the acceptance bar): a dead
    # process loses ZERO not-yet-streaming requests — they reroute —
    # and only mid-stream requests may fail (typed)
    stats_before_kill = dict(router.stats)
    kill_results: dict = {}
    threads = [threading.Thread(target=run_stream,
                                args=(kill_results, j))
               for j in range(8)]
    for t in threads:
        t.start()
    time.sleep(0.15)  # let some streams start, keep some pre-dispatch
    procs[1].kill()  # SIGKILL: no drain, no goodbye
    for t in threads:
        t.join(timeout=120.0)
    dropped = {j: (err.code().name if err else "?")
               for j, (chunks, err) in kill_results.items()
               if err is not None and chunks == 0}
    mid_stream_failures = [j for j, (chunks, err) in kill_results.items()
                           if err is not None and chunks > 0]
    check("mesh: SIGKILL loses zero not-yet-streaming requests "
          "(rerouted instead)", len(kill_results) == 8 and not dropped,
          f"(dropped {dropped}, mid-stream typed failures "
          f"{mid_stream_failures}, rerouted "
          f"{router.stats['rerouted'] - stats_before_kill['rerouted']})")
    deadline = time.monotonic() + 30.0
    while time.monotonic() < deadline and router.routable_count() != 1:
        time.sleep(0.1)
    check("mesh: killed node leaves membership (breaker open)",
          router.routable_count() == 1
          and any(n.state == NODE_OPEN for n in router.nodes),
          f"({[n.snapshot() for n in router.nodes]})")
    code, _ = http_get(mesh_base + "/readyz")
    check("mesh: router readyz 200 after the kill (one healthy node)",
          code == 200, f"(code {code})")

    # ---- placement (ISSUE 14): restart the SIGKILLed backend EMPTY
    # under traffic.  The acceptance bar: the reconciler restores its
    # desired voice set with no router restart and zero client-visible
    # errors for not-yet-streaming requests — and routing stays
    # voice-aware, so the warming node serves only once converged.
    wait_exit(procs[1], 30.0)  # reap the SIGKILLed pid, free the port
    restart_results: dict = {}
    threads = [threading.Thread(target=run_stream,
                                args=(restart_results, j))
               for j in range(4)]
    for t in threads:
        t.start()
    procs[1] = boot_node(1, empty=True)
    check("placement: emptied backend boots ready with no voices",
          wait_node_ready(1))
    for t in threads:
        t.join(timeout=120.0)
    check("placement: zero client-visible errors across the empty "
          "restart",
          all(j in restart_results and restart_results[j][1] is None
              and restart_results[j][0] > 0 for j in range(4)),
          str({j: (r[1].code().name if r[1] else f"{r[0]} chunks")
               for j, r in restart_results.items()}))
    # the reconciler replays LoadVoice onto the rejoined node: its own
    # /readyz voices= line (the reconciler's actual-state channel)
    # must carry the fleet voice again, with no router restart
    restored = False
    deadline = time.monotonic() + 120.0
    while time.monotonic() < deadline and not restored:
        _c, rbody = http_get(
            f"http://127.0.0.1:{node_ports[1][1]}/readyz")
        restored = any(line.startswith("voices=")
                       and voice_id in line for line in rbody.splitlines())
        if not restored:
            time.sleep(0.5)
    check("placement: reconciler replays LoadVoice onto the rejoined "
          "node", restored)
    deadline = time.monotonic() + 60.0
    while time.monotonic() < deadline and \
            placement_gauge("sonata_placement_converged") < 2:
        time.sleep(0.2)
    check("placement: sonata_placement_converged returns to 2",
          placement_gauge("sonata_placement_converged") == 2.0)
    check("placement: sonata_placement_reconcile_ops_total counted the "
          "replay",
          sum(v for lbl, v in parse_prometheus_text(
              http_get(mesh_base + "/metrics")[1]).get(
              "sonata_placement_reconcile_ops_total", [])
              if lbl.get("op") == "load") >= 1.0)
    # the /debug/fleet scoreboard carries the placement table
    code, body = http_get(mesh_base + "/debug/fleet")
    pdoc = (json.loads(body) if code == 200 else {}).get("placement")
    prow = next((v for v in (pdoc or {}).get("voices", [])
                 if v["voice_id"] == voice_id), None)
    check("placement: /debug/fleet placement table shows the voice "
          "converged on both nodes",
          prow is not None and len(prow["assigned"]) == 2
          and len(prow["converged"]) == 2, f"({prow})")
    # and the restored node actually synthesizes the voice again
    restored_id = f"127.0.0.1:{node_ports[1][0]}"
    served_by_restored = False
    deadline = time.monotonic() + 60.0
    while time.monotonic() < deadline and not served_by_restored:
        call = mesh_synth(pb.Utterance(voice_id=voice_id,
                                       text="Serve from the restored "
                                            "node."), timeout=60.0)
        ok = bool(list(call))
        trailers = dict(call.trailing_metadata() or ())
        served_by_restored = ok and \
            trailers.get("x-sonata-node-id") == restored_id
    check("placement: the restored node synthesizes the replayed "
          "voice", served_by_restored)

    # zero healthy nodes is the line the router's readiness must not
    # survive
    procs[1].kill()
    deadline = time.monotonic() + 30.0
    while time.monotonic() < deadline and router.routable_count() != 1:
        time.sleep(0.1)
    procs[0].send_signal(signal.SIGTERM)
    wait_exit(procs[0], 90.0)
    deadline = time.monotonic() + 30.0
    while time.monotonic() < deadline and router.routable_count() != 0:
        time.sleep(0.1)
    code, _ = http_get(mesh_base + "/readyz")
    check("mesh: router readyz 503 at zero healthy nodes", code == 503,
          f"(code {code})")

    mesh_channel.close()
    mesh_server_obj.stop(grace=None)
    mesh_server_obj.sonata_service.shutdown()
    for p in procs:
        if p.poll() is None:
            p.kill()
    for f in node_logs:
        f.close()

    # ---- fleetcache phase (ISSUE 16): the synthesis cache becomes a
    # fleet property.  Cache-affinity routing pins each template to one
    # rendezvous owner (repeats hit that node's cache warm), the
    # owner's hot set replicates to its rendezvous peer riding the
    # prober threads, and SIGKILLing the affinity holder mid-workload
    # leaves zero client-visible errors — the hottest template's next
    # repeat is served WARM by the replication peer.
    fc_ports = [(free_port(), free_port()) for _ in range(2)]
    fc_logs = [open(os.path.join(mesh_cache, f"fcnode{i}.log"), "w")
               for i in range(2)]

    def boot_fc_node(i: int) -> subprocess.Popen:
        env = dict(os.environ, JAX_PLATFORMS="cpu",
                   SMOKE_VOICE_CFG=cfg,
                   JAX_COMPILATION_CACHE_DIR=mesh_cache,
                   SONATA_SYNTH_CACHE_MB="8",
                   MESH_NODE_GRPC_PORT=str(fc_ports[i][0]),
                   MESH_NODE_METRICS_PORT=str(fc_ports[i][1]),
                   MESH_NODE_EMPTY="0")
        return subprocess.Popen(
            [sys.executable, __file__, "--mesh-node-boot"],
            env=env, stdout=fc_logs[i], stderr=fc_logs[i])

    fc_procs = [boot_fc_node(0), boot_fc_node(1)]
    check("fleetcache: cache-enabled backends boot ready",
          wait_readyz(fc_ports[0][1]) and wait_readyz(fc_ports[1][1]))

    os.environ["SONATA_FLEETCACHE"] = "1"
    os.environ["SONATA_FLEETCACHE_REPLICATE_K"] = "4"
    os.environ["SONATA_FLEET_SCRAPE_INTERVAL_S"] = "0.5"
    os.environ["SONATA_MESH_PROBE_INTERVAL_S"] = "0.5"
    try:
        fc_server, fc_grpc_port = create_mesh_server(
            0, backends=[f"127.0.0.1:{g}/{m}" for g, m in fc_ports],
            metrics_port=0, request_timeout_s=60.0)
    finally:
        for k in ("SONATA_FLEETCACHE", "SONATA_FLEETCACHE_REPLICATE_K",
                  "SONATA_MESH_PROBE_INTERVAL_S"):
            del os.environ[k]
    fc_server.start()
    fcs = fc_server.sonata_service.fleetcache
    fc_router = fc_server.sonata_service.router
    fc_fleet = fc_server.sonata_service.fleet
    fc_base = f"http://127.0.0.1:{fc_server.sonata_runtime.http_port}"
    check("fleetcache: router built the fleet-cache tier "
          "(SONATA_FLEETCACHE=1)", fcs is not None)
    fc_channel = grpc.insecure_channel(f"127.0.0.1:{fc_grpc_port}")
    fc_synth = fc_channel.unary_stream(
        "/sonata_grpc.sonata_grpc/SynthesizeUtterance",
        request_serializer=lambda m: m.encode(),
        response_deserializer=pb.SynthesisResult.decode)
    fc_load = fc_channel.unary_unary(
        "/sonata_grpc.sonata_grpc/LoadVoice",
        request_serializer=lambda m: m.encode(),
        response_deserializer=pb.VoiceInfo.decode)
    # LoadVoice THROUGH the router: the fleet-cache tier learns the
    # voice's key inputs (options, speaker map, audio shape) from the
    # wire — affinity routing is inert for voices it has not seen
    fc_info = fc_load(pb.VoicePath(config_path=cfg), timeout=120.0)
    fc_voice = fc_info.voice_id

    def fc_node_metric(i: int, family: str) -> float:
        parsed = parse_prometheus_text(
            http_get(f"http://127.0.0.1:{fc_ports[i][1]}/metrics")[1])
        return sum(v for _lbl, v in parsed.get(family, []))

    # hot-template workload: each template's repeats must stick to the
    # one rendezvous owner and hit its synthesis cache warm
    templates = [f"Fleet cache template number {i} stays hot."
                 for i in range(4)]
    owner_of: dict = {}
    sticky = True
    for _rep in range(3):
        for text in templates:
            call = fc_synth(pb.Utterance(voice_id=fc_voice, text=text),
                            timeout=60.0)
            results = list(call)
            sticky = sticky and bool(results) \
                and len(results[0].wav_samples) > 0
            nid = dict(call.trailing_metadata() or ()).get(
                "x-sonata-node-id")
            owner_of.setdefault(text, set()).add(nid)
    check("fleetcache: every template's repeats stick to one affinity "
          "owner", sticky and all(len(s) == 1 and None not in s
                                  for s in owner_of.values()),
          f"({ {t[:24]: sorted(s) for t, s in owner_of.items()} })")
    check("fleetcache: affinity picks counted on the router",
          fcs is not None and fcs.stat("affinity_hits") >= 8,
          f"({fcs.snapshot() if fcs else None})")
    warm_hits = sum(fc_node_metric(i, "sonata_synth_cache_hits_total")
                    for i in range(2))
    check("fleetcache: repeats hit the owners' caches warm (8 of 12 "
          "requests)", warm_hits >= 8, f"({warm_hits} fleet hits)")

    # the /debug/fleet rollup carries the fleet cache view
    fc_doc: dict = {}
    deadline = time.monotonic() + 30.0
    while time.monotonic() < deadline:
        code, body = http_get(fc_base + "/debug/fleet")
        fc_doc = json.loads(body) if code == 200 else {}
        cr = fc_doc.get("fleet", {}).get("cache") or {}
        if cr.get("nodes_with_cache") == 2 and cr.get("hits", 0) >= 8:
            break
        time.sleep(0.5)
    cr = fc_doc.get("fleet", {}).get("cache") or {}
    check("fleetcache: /debug/fleet rolls up fleet hit ratio and "
          "cache bytes",
          cr.get("nodes_with_cache") == 2 and cr.get("hits", 0) >= 8
          and cr.get("bytes", 0) > 0 and cr.get("hit_ratio") is not None,
          f"({cr})")

    # hot-set replication: the hottest template's entry must land on
    # the rendezvous peer (scrape-advertised hot keys -> prober replay)
    hot_text = templates[0]
    hot_owner = next(iter(owner_of[hot_text]))
    hot_key = fcs.routing_key(
        "utterance", pb.Utterance(voice_id=fc_voice, text=hot_text))
    owner_idx = next(i for i, (g, _m) in enumerate(fc_ports)
                     if f"127.0.0.1:{g}" == hot_owner)
    peer_idx = 1 - owner_idx
    peer_node = next(n for n in fc_router.nodes
                     if n.spec.addr != hot_owner)
    check("fleetcache: hottest template derives a routable cache key",
          hot_key is not None)
    replicated = False
    deadline = time.monotonic() + 60.0
    while time.monotonic() < deadline and not replicated:
        view = fc_fleet.node_cache_view(peer_node)
        replicated = bool(view) and hot_key in (view.get("hot_keys")
                                                or [])
        if not replicated:
            time.sleep(0.5)
    check("fleetcache: hot set replicated to the rendezvous peer",
          replicated, f"(replications={fcs.stat('replications')}, "
          f"failures={fcs.stat('replication_failures')})")

    # SIGKILL the affinity holder mid-workload.  The workload gates
    # issuance for the kill instant itself (a SIGKILL can truncate a
    # stream mid-flight; the mesh phase above already pins that typed
    # path) — the interesting path HERE is that post-kill repeats still
    # route via affinity to the dead owner, fail pre-stream, reroute to
    # the peer, and find its cache already warm.
    peer_hits_before = fc_node_metric(
        peer_idx, "sonata_synth_cache_hits_total")
    gate = threading.Event()
    gate.set()
    stop_at = time.monotonic() + 8.0
    fc_errors: list = []
    progress: dict = {}

    def hot_loop(j: int) -> None:
        n = 0
        while time.monotonic() < stop_at:
            gate.wait(timeout=10.0)
            try:
                call = fc_synth(pb.Utterance(voice_id=fc_voice,
                                             text=hot_text),
                                timeout=60.0)
                results = list(call)
                if not results or len(results[0].wav_samples) == 0:
                    fc_errors.append((j, "empty"))
                n += 1
            except grpc.RpcError as e:
                fc_errors.append((j, e.code().name))
            time.sleep(0.05)
        progress[j] = n

    threads = [threading.Thread(target=hot_loop, args=(j,))
               for j in range(4)]
    for t in threads:
        t.start()
    time.sleep(1.5)          # workload in full swing
    gate.clear()             # park the loops at the gate
    deadline = time.monotonic() + 10.0
    while time.monotonic() < deadline and \
            sum(n.outstanding for n in fc_router.nodes) > 0:
        time.sleep(0.05)
    fc_procs[owner_idx].kill()   # SIGKILL: no drain, no goodbye
    gate.set()               # resume repeats against the dead owner
    for t in threads:
        t.join(timeout=120.0)
    check("fleetcache: zero client-visible errors across the affinity "
          "holder's SIGKILL",
          not fc_errors and len(progress) == 4
          and all(n > 0 for n in progress.values()),
          f"(errors={fc_errors[:4]}, progress={progress})")
    call = fc_synth(pb.Utterance(voice_id=fc_voice, text=hot_text),
                    timeout=60.0)
    results = list(call)
    served_by = dict(call.trailing_metadata() or ()).get(
        "x-sonata-node-id")
    peer_hits_after = fc_node_metric(
        peer_idx, "sonata_synth_cache_hits_total")
    check("fleetcache: hottest template served warm from the "
          "replication peer after the kill",
          bool(results) and len(results[0].wav_samples) > 0
          and served_by == f"127.0.0.1:{fc_ports[peer_idx][0]}"
          and peer_hits_after > peer_hits_before,
          f"(served_by={served_by}, peer hits "
          f"{peer_hits_before}->{peer_hits_after})")
    check("fleetcache: sonata_fleetcache_replications_total exported "
          "on the router",
          sum(v for _l, v in parse_prometheus_text(
              http_get(fc_base + "/metrics")[1]).get(
              "sonata_fleetcache_replications_total", [])) >= 1.0)

    fc_channel.close()
    fc_server.stop(grace=None)
    fc_server.sonata_service.shutdown()
    for p in fc_procs:
        if p.poll() is None:
            p.kill()
    for f in fc_logs:
        f.close()

    # ---- tenancy phase (ISSUE 17): multi-tenant admission + QoS ----
    # One tenant-table backend behind a tenant-table router.  The
    # contract: gold (weight 3) and bronze (weight 1) both serve;
    # bursting bronze past its 2-token bucket draws typed
    # RESOURCE_EXHAUSTED refusals carrying the retry-after-s trailer
    # while gold's TTFB stays inside a generous quiet band; per-tenant
    # burn rows ride the node's /debug/quantiles AND the fleet-merged
    # /debug/fleet; per-tenant padding-waste rows ride /debug/buckets;
    # the router pushes its tenant table to the node (desired-state
    # propagation, remote_revision > 0); and the per-tenant counter
    # families export with exact labels.
    import statistics

    tn_table = json.dumps({"tenants": {
        "gold": {"weight": 3, "qps": 200, "burst": 200},
        "bronze": {"weight": 1, "qps": 2, "burst": 2}}})
    tn_ports = (free_port(), free_port())
    tn_log = open(os.path.join(mesh_cache, "tnnode0.log"), "w")
    tn_env = dict(os.environ, JAX_PLATFORMS="cpu",
                  SMOKE_VOICE_CFG=cfg,
                  JAX_COMPILATION_CACHE_DIR=mesh_cache,
                  SONATA_TENANTS=tn_table,
                  MESH_NODE_GRPC_PORT=str(tn_ports[0]),
                  MESH_NODE_METRICS_PORT=str(tn_ports[1]),
                  MESH_NODE_EMPTY="0")
    tn_proc = subprocess.Popen(
        [sys.executable, __file__, "--mesh-node-boot"],
        env=tn_env, stdout=tn_log, stderr=tn_log)
    check("tenancy: tenant-table backend boots ready",
          wait_readyz(tn_ports[1]))
    os.environ["SONATA_TENANTS"] = tn_table
    os.environ["SONATA_FLEET_SCRAPE_INTERVAL_S"] = "0.5"
    os.environ["SONATA_MESH_PROBE_INTERVAL_S"] = "0.5"
    try:
        tn_server, tn_grpc_port = create_mesh_server(
            0, backends=[f"127.0.0.1:{tn_ports[0]}/{tn_ports[1]}"],
            metrics_port=0, request_timeout_s=60.0)
    finally:
        for k in ("SONATA_TENANTS", "SONATA_FLEET_SCRAPE_INTERVAL_S",
                  "SONATA_MESH_PROBE_INTERVAL_S"):
            del os.environ[k]
    tn_server.start()
    tn_rt = tn_server.sonata_runtime
    tn_base = f"http://127.0.0.1:{tn_rt.http_port}"
    tn_node_base = f"http://127.0.0.1:{tn_ports[1]}"
    check("tenancy: router built the tenant plane and its propagator",
          tn_rt.tenancy is not None
          and tn_server.sonata_service.tenancy_propagator is not None)
    tn_channel = grpc.insecure_channel(f"127.0.0.1:{tn_grpc_port}")
    tn_synth = tn_channel.unary_stream(
        "/sonata_grpc.sonata_grpc/SynthesizeUtterance",
        request_serializer=lambda m: m.encode(),
        response_deserializer=pb.SynthesisResult.decode)
    tn_load = tn_channel.unary_unary(
        "/sonata_grpc.sonata_grpc/LoadVoice",
        request_serializer=lambda m: m.encode(),
        response_deserializer=pb.VoiceInfo.decode)
    tn_voice = tn_load(pb.VoicePath(config_path=cfg),
                       timeout=120.0).voice_id

    def tn_call(text: str, tenant: str) -> dict:
        t0 = time.monotonic()
        call = tn_synth(pb.Utterance(voice_id=tn_voice, text=text),
                        timeout=60.0,
                        metadata=(("x-tenant-id", tenant),))
        first_at = None
        try:
            chunks = []
            for r in call:
                if first_at is None:
                    first_at = time.monotonic()
                chunks.append(r.wav_samples)
            return {"ok": bool(chunks) and len(chunks[0]) > 0,
                    "ttfb": (first_at or time.monotonic()) - t0,
                    "trailers": dict(call.trailing_metadata() or ())}
        except grpc.RpcError as e:
            return {"ok": False, "code": e.code(),
                    "trailers": dict(e.trailing_metadata() or ())}

    # quiet lap: gold alone — its TTFB baseline band
    quiet = [tn_call(f"Gold quiet baseline {i}.", "gold")
             for i in range(3)]
    check("tenancy: quiet gold traffic serves through the router",
          all(r["ok"] for r in quiet),
          f"({[r.get('code') for r in quiet]})")
    quiet_ttfb = statistics.median(r["ttfb"] for r in quiet)

    # burst bronze 4x past its bucket while gold keeps a steady lap:
    # bronze draws typed quota refusals, gold stays in band
    bronze_results: list = []

    def bronze_burst() -> None:
        for i in range(8):
            bronze_results.append(
                tn_call(f"Bronze burst number {i}.", "bronze"))

    bronze_thread = threading.Thread(target=bronze_burst)
    bronze_thread.start()
    busy = [tn_call(f"Gold busy lap {i}.", "gold") for i in range(3)]
    bronze_thread.join(timeout=120.0)
    refused = [r for r in bronze_results if not r["ok"]]
    check("tenancy: bursting bronze draws typed RESOURCE_EXHAUSTED "
          "refusals",
          len(refused) >= 1 and all(
              r.get("code") == grpc.StatusCode.RESOURCE_EXHAUSTED
              for r in refused),
          f"({len(refused)} refused: "
          f"{[getattr(r.get('code'), 'name', None) for r in refused]})")
    check("tenancy: quota refusals carry the retry-after-s trailer",
          bool(refused) and all("retry-after-s" in r["trailers"]
                                for r in refused),
          f"({[r['trailers'] for r in refused[:2]]})")
    busy_ok = [r for r in busy if r["ok"]]
    busy_ttfb = (statistics.median(r["ttfb"] for r in busy_ok)
                 if busy_ok else float("inf"))
    check("tenancy: quiet-tenant TTFB stays in band through the burst",
          len(busy_ok) == 3
          and busy_ttfb <= max(quiet_ttfb * 5.0, quiet_ttfb + 2.0),
          f"(quiet {quiet_ttfb * 1e3:.0f}ms -> busy "
          f"{busy_ttfb * 1e3:.0f}ms)")

    # per-tenant burn rows on the NODE's scope plane (the router
    # stamped x-sonata-tenant, so the node attributes per tenant)
    code, body = http_get(tn_node_base + "/debug/quantiles")
    qdoc = json.loads(body) if code == 200 else {}
    check("tenancy: per-tenant burn rows on the node /debug/quantiles",
          "gold" in (qdoc.get("tenants") or {}),
          f"({sorted((qdoc.get('tenants') or {}))})")
    # per-tenant padding-waste chargeback rows on /debug/buckets
    code, body = http_get(tn_node_base + "/debug/buckets")
    bdoc = json.loads(body) if code == 200 else {}
    waste_tenants = {r.get("tenant")
                     for r in (bdoc.get("tenant_waste") or [])}
    check("tenancy: per-tenant padding-waste rows on /debug/buckets",
          "gold" in waste_tenants, f"({sorted(waste_tenants)})")

    # fleet-merged per-tenant burn on the router's /debug/fleet
    tn_doc: dict = {}
    deadline = time.monotonic() + 30.0
    while time.monotonic() < deadline:
        code, body = http_get(tn_base + "/debug/fleet")
        tn_doc = json.loads(body) if code == 200 else {}
        if (tn_doc.get("fleet", {}).get("tenants") or {}).get("gold"):
            break
        time.sleep(0.5)
    check("tenancy: fleet-merged per-tenant burn on /debug/fleet",
          bool((tn_doc.get("fleet", {}).get("tenants")
                or {}).get("gold")),
          f"({tn_doc.get('fleet', {}).get('tenants')})")

    # desired-state propagation: the router pushed its table revision
    pushed: dict = {}
    deadline = time.monotonic() + 30.0
    while time.monotonic() < deadline:
        code, body = http_get(tn_node_base + "/debug/tenants")
        pushed = json.loads(body) if code == 200 else {}
        if pushed.get("remote_revision", 0) >= 1:
            break
        time.sleep(0.5)
    check("tenancy: router pushed the tenant table to the node "
          "(remote_revision advanced)",
          pushed.get("remote_revision", 0) >= 1,
          f"(node table: revision={pushed.get('revision')}, "
          f"remote_revision={pushed.get('remote_revision')})")

    # per-tenant counter families with exact labels on the router
    parsed = parse_prometheus_text(http_get(tn_base + "/metrics")[1])
    adm = {lbl.get("tenant"): v for lbl, v in parsed.get(
        "sonata_tenant_admitted_total", [])}
    rej = {lbl.get("tenant"): v for lbl, v in parsed.get(
        "sonata_tenant_quota_rejections_total", [])}
    check("tenancy: per-tenant admitted/rejection series on the router",
          adm.get("gold", 0) >= 6 and rej.get("bronze", 0) >= 1,
          f"(admitted={adm}, rejections={rej})")

    tn_channel.close()
    tn_server.stop(grace=None)
    tn_server.sonata_service.shutdown()
    if tn_proc.poll() is None:
        tn_proc.kill()
    tn_log.close()

    # ---- ledger phase (ISSUE 19): per-request wide events ----
    # One ledger-enabled backend behind a ledger-enabled router
    # sampling OK traffic at 0.25.  The contract: the OK capture set is
    # exactly the hash-deterministic keep set (chosen request ids make
    # it pinnable); errors and typed refusals are captured 100% even
    # when their ids hash to "drop"; refusals stamp x-request-id on the
    # wire; /debug/requests filters; querying a routed request by id
    # merges the node-side hop record; and the exemplar gauge points at
    # the latest incident.
    lg_ports = (free_port(), free_port())
    lg_log = open(os.path.join(mesh_cache, "lgnode0.log"), "w")
    lg_env = dict(os.environ, JAX_PLATFORMS="cpu",
                  SMOKE_VOICE_CFG=cfg,
                  JAX_COMPILATION_CACHE_DIR=mesh_cache,
                  SONATA_LEDGER_MB="4",
                  MESH_NODE_GRPC_PORT=str(lg_ports[0]),
                  MESH_NODE_METRICS_PORT=str(lg_ports[1]),
                  MESH_NODE_EMPTY="0")
    lg_proc = subprocess.Popen(
        [sys.executable, __file__, "--mesh-node-boot"],
        env=lg_env, stdout=lg_log, stderr=lg_log)
    check("ledger: ledger-enabled backend boots ready",
          wait_readyz(lg_ports[1]))
    os.environ["SONATA_LEDGER_MB"] = "4"
    os.environ["SONATA_LEDGER_SAMPLE"] = "0.25"
    try:
        lg_server, lg_grpc_port = create_mesh_server(
            0, backends=[f"127.0.0.1:{lg_ports[0]}/{lg_ports[1]}"],
            metrics_port=0, request_timeout_s=60.0)
    finally:
        for k in ("SONATA_LEDGER_MB", "SONATA_LEDGER_SAMPLE"):
            del os.environ[k]
    lg_server.start()
    lg_rt = lg_server.sonata_runtime
    lg_base = f"http://127.0.0.1:{lg_rt.http_port}"
    check("ledger: router built the request ledger at sample=0.25",
          lg_rt.ledger is not None and lg_rt.ledger.sample == 0.25)
    lg_channel = grpc.insecure_channel(f"127.0.0.1:{lg_grpc_port}")
    lg_synth = lg_channel.unary_stream(
        "/sonata_grpc.sonata_grpc/SynthesizeUtterance",
        request_serializer=lambda m: m.encode(),
        response_deserializer=pb.SynthesisResult.decode)
    lg_loadv = lg_channel.unary_unary(
        "/sonata_grpc.sonata_grpc/LoadVoice",
        request_serializer=lambda m: m.encode(),
        response_deserializer=pb.VoiceInfo.decode)
    lg_voice = lg_loadv(pb.VoicePath(config_path=cfg),
                        timeout=120.0).voice_id

    def lg_call(rid: str, text: str, voice: str = "") -> dict:
        call = lg_synth(
            pb.Utterance(voice_id=voice or lg_voice, text=text),
            timeout=60.0, metadata=(("x-request-id", rid),))
        try:
            chunks = [r.wav_samples for r in call]
            return {"ok": bool(chunks) and len(chunks[0]) > 0,
                    "trailers": dict(call.trailing_metadata() or ())}
        except grpc.RpcError as e:
            return {"ok": False, "code": e.code(),
                    "trailers": dict(e.trailing_metadata() or ())}

    lg_ok_ids = [f"smoke-lg-ok-{i:02d}" for i in range(8)]
    lg_served = [lg_call(rid, f"Ledger lap {i}.")
                 for i, rid in enumerate(lg_ok_ids)]
    check("ledger: routed OK traffic serves",
          all(r["ok"] for r in lg_served),
          f"({[r.get('code') for r in lg_served]})")
    lg_expected = {rid for rid in lg_ok_ids
                   if lg_rt.ledger.sample_decision(rid)}
    lg_captured = {r["request_id"]
                   for r in lg_rt.ledger.query(outcome="ok", limit=100)
                   if r["request_id"] in set(lg_ok_ids)}
    check("ledger: OK capture set is exactly the deterministic sample "
          "keep set",
          lg_captured == lg_expected and 0 < len(lg_captured) < 8,
          f"(captured {sorted(lg_captured)}, "
          f"expected {sorted(lg_expected)})")
    check("ledger: sampled-out OK records are counted, not lost",
          lg_rt.ledger.stat("sampled_out") >= len(lg_ok_ids)
          - len(lg_expected)
          and lg_rt.ledger.outcome_total("ok") >= len(lg_ok_ids),
          f"(sampled_out={lg_rt.ledger.stat('sampled_out')})")

    # an unknown voice is an ERROR record — captured despite a
    # request id that hashes to "drop" at sample=0.25
    err_res = lg_call("smoke-lg-ref-0", "No such voice.",
                      voice="no-such-voice")
    err_rows = lg_rt.ledger.query(request_id="smoke-lg-ref-0", limit=5)
    check("ledger: error outcome captured at 100% despite sampling",
          not err_res["ok"]
          and not lg_rt.ledger.sample_decision("smoke-lg-ref-0")
          and len(err_rows) == 1
          and err_rows[0]["outcome"] == "error",
          f"({err_rows})")

    # drain the router: every subsequent request draws the typed
    # ``draining`` refusal — 100% captured, id stamped on the wire
    lg_rt.drain.begin("smoke-ledger-phase")
    lg_refused = [lg_call(f"smoke-lg-ref-{i}", "Refuse me.")
                  for i in (1, 2)]
    check("ledger: draining refusals are typed UNAVAILABLE",
          all(not r["ok"] and r.get("code") ==
              grpc.StatusCode.UNAVAILABLE for r in lg_refused),
          f"({[getattr(r.get('code'), 'name', None) for r in lg_refused]})")
    check("ledger: refusals stamp x-request-id on the wire",
          [r["trailers"].get("x-request-id") for r in lg_refused]
          == ["smoke-lg-ref-1", "smoke-lg-ref-2"],
          f"({[r['trailers'] for r in lg_refused]})")
    ref_rows = lg_rt.ledger.query(outcome="refused", limit=10)
    check("ledger: refusal records captured at 100% with the typed "
          "kind",
          {r["request_id"] for r in ref_rows}
          >= {"smoke-lg-ref-1", "smoke-lg-ref-2"}
          and all(r["refusal"] == "draining" for r in ref_rows),
          f"({ref_rows})")

    # /debug/requests: outcome filter + router-merge of the node-side
    # hop record when querying one routed request by id
    code, body = http_get(lg_base + "/debug/requests?outcome=refused")
    lg_doc = json.loads(body) if code == 200 else {}
    check("ledger: /debug/requests filters by outcome",
          code == 200 and lg_doc.get("count", 0) >= 2
          and all(r["outcome"] == "refused"
                  for r in lg_doc.get("records", [])),
          f"(code {code}, count {lg_doc.get('count')})")
    merged_id = sorted(lg_expected)[0]
    code, body = http_get(lg_base + f"/debug/requests?id={merged_id}")
    lg_doc = json.loads(body) if code == 200 else {}
    lg_recs = lg_doc.get("records", [])
    check("ledger: by-id query merges the node-side hop record",
          code == 200 and len(lg_recs) == 1
          and (lg_recs[0].get("node_record") or {}).get("request_id")
          == merged_id,
          f"({lg_recs})")

    # exemplar gauge: one series per incident kind, pointing at the
    # latest incident's request id
    parsed = parse_prometheus_text(http_get(lg_base + "/metrics")[1])
    exemplars = {lbl.get("kind"): lbl.get("request_id")
                 for lbl, _v in parsed.get("sonata_ledger_exemplar", [])}
    check("ledger: exemplar gauge points at the latest refusal",
          exemplars.get("refusal") == "smoke-lg-ref-2",
          f"({exemplars})")
    check("ledger: per-outcome record totals exported",
          {lbl.get("outcome"): v for lbl, v in parsed.get(
              "sonata_ledger_records_total", [])}.get("refused", 0) >= 2)

    lg_channel.close()
    lg_server.stop(grace=None)
    lg_server.sonata_service.shutdown()
    if lg_proc.poll() is None:
        lg_proc.kill()
    lg_log.close()

    if failures:
        print(f"smoke: {len(failures)} FAILED: {failures}")
        return 1
    print("smoke: all checks passed")
    return 0


if __name__ == "__main__":
    import argparse

    ap = argparse.ArgumentParser()
    ap.add_argument("--warmup-artifact", default=None,
                    help="write the warm-restart cold/warm numbers to "
                         "this path (the committed WARMUP_rNN.json); "
                         "omitted in CI so the artifact never churns")
    ap.add_argument("--warm-restart-boot", action="store_true",
                    help=argparse.SUPPRESS)  # subprocess entry
    ap.add_argument("--iteration-boot", action="store_true",
                    help=argparse.SUPPRESS)  # subprocess entry
    ap.add_argument("--mesh-node-boot", action="store_true",
                    help=argparse.SUPPRESS)  # subprocess entry
    cli_args = ap.parse_args()
    if cli_args.warm_restart_boot:
        sys.exit(warm_restart_boot())
    if cli_args.iteration_boot:
        sys.exit(iteration_boot())
    if cli_args.mesh_node_boot:
        sys.exit(mesh_node_boot())
    sys.exit(main(cli_args))
