"""Secondary benchmark: streaming time-to-first-byte and concurrent load.

The driver's headline metric comes from ``bench.py`` (batched RTF); this
script measures the other BASELINE.md configs: realtime-stream TTFB (first
audio chunk latency, gRPC default chunk 55/pad 3) and aggregate
audio-seconds/second under concurrent streaming load.  Prints one JSON line
per metric.

``--cache-artifact PATH`` runs the **cached-replay arm** instead
(ISSUE 15): a real in-process gRPC server with
``SONATA_SYNTH_CACHE_MB`` armed, measuring hit-vs-miss first-chunk TTFB
p50 over the wire (interleaved arms) and the hit ratio under a
Zipf-repeated workload — the committed ``CACHE_rNN.json`` artifact
(folded into BENCH_TREND by the CACHE family).

``--ledger-artifact PATH`` runs the **request-ledger arm** instead
(ISSUE 19): two in-process gRPC servers — one with the wide-event
ledger armed at worst-case capture (``SONATA_LEDGER_MB=4``, sample
1.0), one ledger-off — measuring interleaved first-chunk TTFB p50 over
the wire.  The headline ``ledger_overhead`` ratio (on p50 / off p50)
pins the always-on observability budget; the committed
``LEDGER_rNN.json`` artifact is folded into BENCH_TREND by the LEDGER
family.
"""

from __future__ import annotations

import json
import statistics
import sys
import time

SENTENCE = ("Streaming synthesis should deliver the first chunk quickly "
            "while the rest of the utterance is still being decoded.")


def run_cache_arm(artifact_path: str) -> None:
    """The cached-replay arm: hit-vs-miss TTFB and Zipf hit ratio
    against a live cache-enabled server (the grpc layer owns the cache,
    so the bench drives the real request path, not the synthesizer)."""
    import os
    import random
    import tempfile
    from pathlib import Path

    sys.path.insert(0, str(Path(__file__).resolve().parent / "tests"))
    import grpc

    from sonata_tpu.frontends import grpc_messages as pb
    from sonata_tpu.frontends.grpc_server import create_server
    from sonata_tpu.utils.jax_cache import enable_persistent_compile_cache
    from voices import write_tiny_voice

    enable_persistent_compile_cache()
    cfg = str(write_tiny_voice(Path(tempfile.mkdtemp(prefix="cache_bench"))))
    os.environ["SONATA_SYNTH_CACHE_MB"] = "64"
    try:
        server, port = create_server(0, metrics_port=0,
                                     request_timeout_s=120.0)
    finally:
        del os.environ["SONATA_SYNTH_CACHE_MB"]
    server.start()
    cache = server.sonata_runtime.synth_cache
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

    def first_chunk_ttfb(text: str) -> float:
        t0 = time.perf_counter()
        stream = realtime(pb.Utterance(voice_id=info.voice_id, text=text),
                          timeout=120.0)
        next(iter(stream))
        dt = time.perf_counter() - t0
        for _chunk in stream:
            pass
        return dt

    # medium-length template texts (the toy test voice synthesizes
    # unrealistically fast on five-word strings; a production VITS pays
    # hundreds of ms of encode+acoustics before the first chunk either
    # way — the hit side is text-length-independent)
    def template(tag) -> str:
        return (f"Template number {tag}: your delivery arrives this "
                "afternoon between two and four, reply with the word "
                "reschedule if that window no longer works for you.")

    # warm the synthesis path on sacrificial texts of the same length
    # class, so the miss arm below measures warm-path synthesis (not
    # first-shape XLA compiles) — the honest baseline a hit displaces
    for i in range(3):
        first_chunk_ttfb(template(f"warm-{i}"))

    # interleaved hit/miss arms: one hot text (primed once), fresh
    # texts for the miss arm — clock drift hits both arms equally
    hot = template("hot")
    first_chunk_ttfb(hot)  # prime the entry
    hits, misses = [], []
    for i in range(10):
        misses.append(first_chunk_ttfb(template(f"fresh-{i}")))
        hits.append(first_chunk_ttfb(hot))
    p50_hit = statistics.median(hits)
    p50_miss = statistics.median(misses)
    rows = [
        {"metric": "cached_replay_ttfb_p50_hit_ms",
         "value": round(p50_hit * 1e3, 3), "unit": "ms",
         "vs_baseline": None, "runs": len(hits)},
        {"metric": "cached_replay_ttfb_p50_miss_ms",
         "value": round(p50_miss * 1e3, 3), "unit": "ms",
         "vs_baseline": None, "runs": len(misses)},
        {"metric": "cache_miss_over_hit_speedup",
         "value": round(p50_miss / max(p50_hit, 1e-9), 2),
         "unit": "ratio_miss_over_hit",
         "vs_baseline": None},
    ]

    # Zipf-repeated workload (the consumer-traffic shape: notification
    # templates and UI strings repeat heavily): 16 distinct texts,
    # rank^-1.1 weights, 80 seeded draws — hit ratio from the cache's
    # own books over exactly this workload's lookups
    texts = [template(f"zipf-{i}") for i in range(16)]
    weights = [1.0 / (rank + 1) ** 1.1 for rank in range(len(texts))]
    rng = random.Random(15)
    draws = rng.choices(range(len(texts)), weights=weights, k=80)
    h0, m0 = cache.stat("hits"), cache.stat("misses")
    for idx in draws:
        first_chunk_ttfb(texts[idx])
    zipf_hits = cache.stat("hits") - h0
    zipf_lookups = zipf_hits + cache.stat("misses") - m0
    rows.append({
        "metric": "zipf_hit_ratio",
        "value": round(zipf_hits / max(zipf_lookups, 1), 4),
        "unit": "hits_over_lookups",
        "vs_baseline": None,
        "distinct_texts": len(texts), "requests": len(draws),
        "zipf_exponent": 1.1})
    for row in rows:
        print(json.dumps(row))
    artifact = {
        "bench": "synth_cache",
        "host": "ci-cpu",
        "notes": ("bench_streaming --cache-artifact: in-process gRPC "
                  "server, SONATA_SYNTH_CACHE_MB=64, tiny test voice; "
                  "hit/miss TTFB p50 from interleaved first-chunk "
                  "latencies over the loopback wire (10 runs per arm, "
                  "warm synthesis path); zipf_hit_ratio from a seeded "
                  "rank^-1.1 workload (16 texts, 80 requests) over the "
                  "cache's own hit/miss books.  The speedup ratio is "
                  "the headline (both arms share host noise); absolute "
                  "TTFBs are supporting per the r11/r12 convention."),
        "configs": {"synth_cache": {"results": [
            {k: row[k] for k in ("metric", "value")} for row in rows]}},
    }
    Path(artifact_path).write_text(
        json.dumps(artifact, indent=1, sort_keys=True) + "\n",
        encoding="utf-8")
    print(f"cache bench: wrote {artifact_path}")
    channel.close()
    server.stop(grace=None)
    server.sonata_service.shutdown()


def run_ledger_arm(artifact_path: str) -> None:
    """The request-ledger arm (ISSUE 19): first-chunk TTFB with the
    wide-event ledger on (worst-case: sample=1.0, every record kept)
    vs off, interleaved over the wire against two otherwise-identical
    in-process servers.  The ratio is the committed always-on budget —
    the ledger finalizes records off the chunk path, so on/off should
    be statistically indistinguishable (the ≤1.02 bar)."""
    import os
    import tempfile
    from pathlib import Path

    sys.path.insert(0, str(Path(__file__).resolve().parent / "tests"))
    import grpc

    from sonata_tpu.frontends import grpc_messages as pb
    from sonata_tpu.frontends.grpc_server import create_server
    from sonata_tpu.utils.jax_cache import enable_persistent_compile_cache
    from voices import write_tiny_voice

    enable_persistent_compile_cache()
    cfg = str(write_tiny_voice(
        Path(tempfile.mkdtemp(prefix="ledger_bench"))))

    def boot(with_ledger: bool):
        if with_ledger:
            os.environ["SONATA_LEDGER_MB"] = "4"
            os.environ["SONATA_LEDGER_SAMPLE"] = "1"
        try:
            server, port = create_server(0, metrics_port=0,
                                         request_timeout_s=120.0)
        finally:
            if with_ledger:
                del os.environ["SONATA_LEDGER_MB"]
                del os.environ["SONATA_LEDGER_SAMPLE"]
        server.start()
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
        return server, channel, realtime, info.voice_id

    on_server, on_channel, on_rpc, on_voice = boot(with_ledger=True)
    off_server, off_channel, off_rpc, off_voice = boot(with_ledger=False)
    assert on_server.sonata_runtime.ledger is not None
    assert off_server.sonata_runtime.ledger is None

    def first_chunk_ttfb(rpc, voice_id: str, text: str,
                         rid: str) -> float:
        t0 = time.perf_counter()
        stream = rpc(pb.Utterance(voice_id=voice_id, text=text),
                     timeout=120.0,
                     metadata=(("x-request-id", rid),))
        next(iter(stream))
        dt = time.perf_counter() - t0
        for _chunk in stream:
            pass
        return dt

    def template(tag) -> str:
        return (f"Ledger run {tag}: your delivery arrives this "
                "afternoon between two and four, reply with the word "
                "reschedule if that window no longer works for you.")

    # warm both servers' synthesis paths on sacrificial texts so the
    # measured arms compare warm-path TTFB, not first-shape compiles
    for i in range(3):
        first_chunk_ttfb(on_rpc, on_voice, template(f"warm-{i}"),
                         f"bench-warm-on-{i}")
        first_chunk_ttfb(off_rpc, off_voice, template(f"warm-{i}"),
                         f"bench-warm-off-{i}")

    on_ts, off_ts = [], []
    for i in range(32):  # interleaved arms: drift hits both equally;
        # alternating which arm goes first cancels any per-iteration
        # warm-cache bias toward the second measurement
        arms = [(off_ts, off_rpc, off_voice, "off"),
                (on_ts, on_rpc, on_voice, "on")]
        if i % 2:
            arms.reverse()
        for sink, rpc, voice, tag in arms:
            sink.append(first_chunk_ttfb(rpc, voice,
                                         template(f"run-{i}"),
                                         f"bench-{tag}-{i:02d}"))
    p50_on = statistics.median(on_ts)
    p50_off = statistics.median(off_ts)
    ledger = on_server.sonata_runtime.ledger
    captured = len(ledger.query(outcome="ok", limit=1000))
    rows = [
        {"metric": "ledger_on_ttfb_p50_ms",
         "value": round(p50_on * 1e3, 3), "unit": "ms",
         "vs_baseline": None, "runs": len(on_ts)},
        {"metric": "ledger_off_ttfb_p50_ms",
         "value": round(p50_off * 1e3, 3), "unit": "ms",
         "vs_baseline": None, "runs": len(off_ts)},
        {"metric": "ledger_overhead",
         "value": round(p50_on / max(p50_off, 1e-9), 4),
         "unit": "ratio_ledger_on_over_off",
         "vs_baseline": None,
         "records_captured": captured},
    ]
    for row in rows:
        print(json.dumps(row))
    artifact = {
        "bench": "request_ledger",
        "host": "ci-cpu",
        "notes": ("bench_streaming --ledger-artifact: two in-process "
                  "gRPC servers (SONATA_LEDGER_MB=4 sample=1.0 vs "
                  "ledger-off), tiny test voice; first-chunk TTFB p50 "
                  "from interleaved runs over the loopback wire (12 "
                  "runs per arm, warm synthesis path).  The "
                  "ledger_overhead ratio is the headline (both arms "
                  "share host noise) and pins the always-on wide-event "
                  "budget at <= 1.02; absolute TTFBs are supporting "
                  "per the r11/r12 convention."),
        "configs": {"request_ledger": {"results": [
            {k: row[k] for k in ("metric", "value")} for row in rows]}},
    }
    Path(artifact_path).write_text(
        json.dumps(artifact, indent=1, sort_keys=True) + "\n",
        encoding="utf-8")
    print(f"ledger bench: wrote {artifact_path}")
    for channel, server in ((on_channel, on_server),
                            (off_channel, off_server)):
        channel.close()
        server.stop(grace=None)
        server.sonata_service.shutdown()


def main() -> None:
    import argparse

    ap = argparse.ArgumentParser()
    ap.add_argument("--skip-ab", action="store_true",
                    help="skip the in-bench batch-mode/pipeline A/B "
                         "(three extra voices; the precision-arm "
                         "configs in bench_cpu only need the headline "
                         "metrics)")
    ap.add_argument("--cache-artifact", default=None, metavar="PATH",
                    help="run ONLY the cached-replay arm (ISSUE 15) "
                         "against a live cache-enabled gRPC server and "
                         "write the CACHE_rNN.json artifact here")
    ap.add_argument("--ledger-artifact", default=None, metavar="PATH",
                    help="run ONLY the request-ledger overhead arm "
                         "(ISSUE 19) against ledger-on/off gRPC "
                         "servers and write the LEDGER_rNN.json "
                         "artifact here")
    args = ap.parse_args()

    from bench import device_summary
    from sonata_tpu.utils.jax_cache import enable_persistent_compile_cache

    enable_persistent_compile_cache()
    # the platform JAX selected, named before any metric; a backend that
    # does not initialize is JAX's own uncaught error (no fallback)
    print(json.dumps({"device": device_summary()}))
    if args.cache_artifact:
        run_cache_arm(args.cache_artifact)
        return
    if args.ledger_artifact:
        run_ledger_arm(args.ledger_artifact)
        return

    from sonata_tpu.models import PiperVoice
    from sonata_tpu.synth import SpeechSynthesizer

    voice = PiperVoice.random(seed=0, audio={"sample_rate": 22050,
                                             "quality": "high"})
    synth = SpeechSynthesizer(voice)

    # warmup: compile encode/acoustics/window-decode executables, including
    # the coalesced-batch shapes the concurrent phases below will hit
    voice.prewarm(texts=[SENTENCE], streaming=True, chunk_size=55,
                  chunk_padding=3)
    for _chunk in synth.synthesize_streamed(SENTENCE, chunk_size=55,
                                            chunk_padding=3):
        pass

    ttfbs = []
    for _ in range(5):
        t0 = time.perf_counter()
        stream = synth.synthesize_streamed(SENTENCE, chunk_size=55,
                                           chunk_padding=3)
        next(iter(stream))
        ttfbs.append(time.perf_counter() - t0)
        for _chunk in stream:  # drain
            pass
    p50 = statistics.median(ttfbs)
    print(json.dumps({
        "metric": "streaming_ttfb_p50",
        "value": round(p50 * 1000.0, 2),
        "unit": "ms",
        "vs_baseline": None,  # the reference publishes no TTFB numbers
    }))

    # tracing overhead on the default config (the ≤2% always-on budget):
    # identical single-stream TTFB runs with a request trace active vs
    # not, interleaved so clock drift hits both arms equally.  Traced
    # runs exercise the real span set (phonemize, encode-ids,
    # encode-acoustics, decode-window per chunk, postprocess no-op).
    from sonata_tpu.serving import tracing as _tracing

    _tracer = _tracing.Tracer(enabled=True, recent=8, slowest=4)

    def _one_ttfb(traced: bool) -> float:
        t0 = time.perf_counter()
        if traced:
            with _tracer.trace_request("bench-stream"):
                stream = synth.synthesize_streamed(SENTENCE,
                                                   chunk_size=55,
                                                   chunk_padding=3)
                next(iter(stream))
                dt = time.perf_counter() - t0
                for _chunk in stream:
                    pass
        else:
            stream = synth.synthesize_streamed(SENTENCE, chunk_size=55,
                                               chunk_padding=3)
            next(iter(stream))
            dt = time.perf_counter() - t0
            for _chunk in stream:
                pass
        return dt

    traced_ts, untraced_ts = [], []
    for i in range(18):  # alternate arms
        (traced_ts if i % 2 == 0 else untraced_ts).append(
            _one_ttfb(traced=i % 2 == 0))
    p50_traced = statistics.median(traced_ts)
    p50_untraced = statistics.median(untraced_ts)
    sample = _tracer.recent_traces()
    print(json.dumps({
        "metric": "trace_overhead",
        "value": round(p50_traced / max(p50_untraced, 1e-9), 4),
        "unit": "ratio_traced_over_untraced",
        "vs_baseline": None,
        "ttfb_p50_traced_ms": round(p50_traced * 1e3, 2),
        "ttfb_p50_untraced_ms": round(p50_untraced * 1e3, 2),
        "spans_per_trace": (len(sample[0].spans_snapshot())
                            if sample else 0),
        "runs_per_arm": len(traced_ts),
    }))

    # scope overhead (the ISSUE-7 aggregation plane, same ≤2% bar as
    # tracing): identical traced single-stream TTFB runs with the scope
    # installed (trace-finish feed + sketches + 1 Hz recorder live) vs
    # uninstalled, interleaved so clock drift hits both arms equally.
    from sonata_tpu.serving import scope as _scope_mod

    _scope = _scope_mod.Scope()
    scoped_ts, unscoped_ts = [], []
    for i in range(18):  # alternate arms
        enabled = i % 2 == 0
        if enabled:
            _scope_mod.install(_scope)
            _scope.start()
        try:
            dt = _one_ttfb(traced=True)
        finally:
            if enabled:
                _scope_mod.uninstall(_scope)
                _scope.close()
        (scoped_ts if enabled else unscoped_ts).append(dt)
    p50_scoped = statistics.median(scoped_ts)
    p50_unscoped = statistics.median(unscoped_ts)
    print(json.dumps({
        "metric": "scope_overhead",
        "value": round(p50_scoped / max(p50_unscoped, 1e-9), 4),
        "unit": "ratio_scoped_over_unscoped",
        "vs_baseline": None,
        "ttfb_p50_scoped_ms": round(p50_scoped * 1e3, 2),
        "ttfb_p50_unscoped_ms": round(p50_unscoped * 1e3, 2),
        "stage_observations": _scope._stages["e2e"]["1h"].merged().count,
        "runs_per_arm": len(scoped_ts),
    }))

    # concurrent streaming load: N clients, aggregate audio throughput
    import concurrent.futures

    n_clients = 4

    def run_stream(i: int) -> float:
        total = 0
        for chunk in synth.synthesize_streamed(SENTENCE, chunk_size=55,
                                               chunk_padding=3):
            total += len(chunk.samples)
        return total / synth.audio_output_info().sample_rate

    t0 = time.perf_counter()
    with concurrent.futures.ThreadPoolExecutor(n_clients) as ex:
        seconds = list(ex.map(run_stream, range(n_clients)))
    elapsed = time.perf_counter() - t0
    print(json.dumps({
        "metric": "concurrent_streaming_audio_s_per_s",
        "value": round(sum(seconds) / elapsed, 2),
        "unit": "audio_seconds_per_second",
        "vs_baseline": None,
    }), file=sys.stdout)

    # TTFB degradation under load: p50 first-chunk latency with N
    # concurrent streams vs the single-stream p50 above.  The shared
    # decode coalescer should keep this ratio well below N (the
    # reference's thread-per-stream serving degrades linearly).
    for n in (4, 8):
        def first_chunk_latency(i: int) -> float:
            t = time.perf_counter()
            stream = synth.synthesize_streamed(SENTENCE, chunk_size=55,
                                               chunk_padding=3)
            next(iter(stream))
            dt = time.perf_counter() - t
            for _chunk in stream:
                pass
            return dt

        with concurrent.futures.ThreadPoolExecutor(n) as ex:
            lats = list(ex.map(first_chunk_latency, range(n)))
        print(json.dumps({
            "metric": f"streaming_ttfb_p50_at_{n}_streams",
            "value": round(statistics.median(lats) * 1000.0, 2),
            "unit": "ms",
            "vs_baseline": None,
        }))
    # per-dispatch observability: what the backend-adaptive policy chose
    # and how many requests actually shared each device dispatch
    stats = voice.dispatch_stats()
    for stage in ("stream_decode", "stream_stage"):
        s = stats.get(stage)
        if s is not None:
            print(json.dumps({
                "metric": f"{stage}_coalescing_ratio",
                "value": s["coalescing_ratio"],
                "unit": "requests_per_dispatch",
                "vs_baseline": None,
            }))
    pol = stats.get("policy")
    if pol is not None:
        print(json.dumps({
            "metric": "dispatch_policy_coalesce",
            "value": 1.0 if pol["coalesce"] else 0.0,
            "unit": "bool",
            "vs_baseline": None,
            "policy": {k: pol[k] for k in (
                "backend", "source", "stream_decode_max_batch",
                "stream_decode_max_wait_ms", "stream_stage_max_batch",
                "stream_stage_max_wait_ms", "scheduler_max_batch",
                "scheduler_max_wait_ms")},
            "probe": pol.get("probe"),
        }))

    # ----------------------------------------------------------------
    # iteration-vs-dispatch AND pipelined-vs-sync A/B: same host, fresh
    # voice per arm, coalescing forced ON for all (the arms differ in
    # HOW a batch forms/fetches, not whether; the CPU default policy
    # would give every arm per-request dispatch and measure nothing),
    # interleaved runs at 1/4/8 streams so host noise hits all arms
    # equally.  Three arms:
    #   dispatch        — PR-1 wave batching
    #   iteration       — persistent loop, pipelined fetch (the default:
    #                     SONATA_ITER_PIPELINE=1)
    #   iteration_sync  — persistent loop, synchronous fetch
    #                     (SONATA_ITER_PIPELINE=0)
    # Primary metrics on this 2-vCPU host: the per-iteration padding
    # ratio and the fetch-overlap fraction (both deterministic engine
    # accounting, above noise); TTFB p50s are reported but carry the
    # documented 2x run-to-run swing under oversubscription.
    # ----------------------------------------------------------------
    if args.skip_ab:
        return
    import os as _os

    AB_ARMS = {
        "dispatch": {"SONATA_BATCH_MODE": "dispatch"},
        "iteration": {"SONATA_BATCH_MODE": "iteration",
                      "SONATA_ITER_PIPELINE": "1"},
        "iteration_sync": {"SONATA_BATCH_MODE": "iteration",
                           "SONATA_ITER_PIPELINE": "0"},
    }
    _saved_env = {k: _os.environ.get(k)
                  for k in ("SONATA_BATCH_MODE", "SONATA_DISPATCH_POLICY",
                            "SONATA_ITER_PIPELINE")}
    _os.environ["SONATA_DISPATCH_POLICY"] = "on"

    def _set_arm(arm: str) -> None:
        for k, v in AB_ARMS[arm].items():
            _os.environ[k] = v

    ab_voices = {}
    try:
        for arm in AB_ARMS:
            _set_arm(arm)
            vm = PiperVoice.random(seed=0, audio={"sample_rate": 22050,
                                                  "quality": "high"})
            vm.prewarm(texts=[SENTENCE], streaming=True, chunk_size=55,
                       chunk_padding=3)
            ab_voices[arm] = vm

        def _one_run(arm: str, n: int) -> float:
            _set_arm(arm)
            vm = ab_voices[arm]
            sm = SpeechSynthesizer(vm)

            def first_chunk(i: int) -> float:
                t = time.perf_counter()
                stream = sm.synthesize_streamed(SENTENCE, chunk_size=55,
                                                chunk_padding=3)
                next(iter(stream))
                dt = time.perf_counter() - t
                for _chunk in stream:
                    pass
                return dt

            if n == 1:
                return first_chunk(0)
            with concurrent.futures.ThreadPoolExecutor(n) as ex:
                return statistics.median(ex.map(first_chunk, range(n)))

        RUNS_PER_ARM = 3
        ab_p50s: dict = {}
        for n in (1, 4, 8):
            p50s = {arm: [] for arm in AB_ARMS}
            for _rep in range(RUNS_PER_ARM):
                for arm in AB_ARMS:  # interleaved
                    p50s[arm].append(_one_run(arm, n))
            for arm in AB_ARMS:
                ab_p50s[(arm, n)] = statistics.median(p50s[arm])
                print(json.dumps({
                    "metric": f"batch_mode_ab_ttfb_p50_at_{n}_streams_"
                              f"{arm}",
                    "value": round(ab_p50s[(arm, n)] * 1000.0, 2),
                    "unit": "ms",
                    "vs_baseline": None,
                    "runs": RUNS_PER_ARM,
                }))
        for n in (4, 8):
            print(json.dumps({
                # name avoids the trend tool's direction fragments:
                # this is a report-only ratio (sync-fetch p50 over
                # pipelined p50 — above 1.0 means pipelining won)
                "metric": f"iter_pipeline_ab_sync_over_pipelined_"
                          f"at_{n}_streams",
                "value": round(ab_p50s[("iteration_sync", n)]
                               / max(ab_p50s[("iteration", n)], 1e-9), 4),
                "unit": "ratio_sync_over_pipelined",
                "vs_baseline": None,
                "note": "supporting evidence on a 2-vCPU host "
                        "(documented 2x oversubscription swings)",
            }))

        def _padding_ratio(stats: dict) -> float:
            rows = stats.get("rows", 0)
            padded = stats.get("padded_rows", 0)
            return round(padded / max(rows + padded, 1), 4)

        ratios = {}
        for arm in AB_ARMS:
            st = ab_voices[arm].dispatch_stats()
            s = st["iteration"] if arm.startswith("iteration") \
                else st["stream_decode"]
            ratios[arm] = _padding_ratio(s or {})
            print(json.dumps({
                "metric": f"window_decode_padding_ratio_{arm}",
                "value": ratios[arm],
                "unit": "padding_rows_over_total_rows",
                "vs_baseline": None,
                "engine_stats": s,
            }))
        print(json.dumps({
            "metric": "iteration_vs_dispatch_padding_ratio",
            "value": (round(ratios["iteration"]
                            / max(ratios["dispatch"], 1e-9), 4)
                      if ratios["dispatch"] else None),
            "unit": "ratio_iteration_over_dispatch",
            "vs_baseline": None,
        }))
        # fetch-overlap fraction: of the iterations each loop ran, how
        # many dispatched while the previous iteration's fetch was
        # still in flight — deterministic engine accounting, the
        # pipelined arm's above-noise headline (sync arm is 0 by
        # construction)
        for arm in ("iteration", "iteration_sync"):
            s = ab_voices[arm].dispatch_stats()["iteration"] or {}
            overlap = round(s.get("fetch_overlapped", 0)
                            / max(s.get("iterations", 0), 1), 4)
            suffix = "" if arm == "iteration" else "_sync"
            print(json.dumps({
                "metric": f"iter_fetch_overlap{suffix}",
                "value": overlap,
                "unit": "overlapped_iterations_over_iterations",
                "vs_baseline": None,
                "engine_stats": {k: s.get(k) for k in
                                 ("iterations", "fetch_overlapped",
                                  "rows", "padded_rows")},
            }))
    finally:
        for vm in ab_voices.values():
            vm.close()
        for k, old in _saved_env.items():
            if old is None:
                _os.environ.pop(k, None)
            else:
                _os.environ[k] = old

    # replica-pool row: batched throughput fanned across one replica per
    # local device (1 on a single-chip host — the row then documents the
    # single-replica baseline; forced multi-device CPU hosts show the
    # router spreading work).  Reported with the replica count so runs
    # on different host shapes stay comparable.
    import jax

    from sonata_tpu.serving import ReplicaPool

    pool = ReplicaPool.for_voice(voice)
    try:
        phon = list(voice.phonemize_text(SENTENCE))
        pool.speak_many(phon)  # warm every routed path once
        burst = phon * 8
        t0 = time.perf_counter()
        audio_s = sum(len(a.samples) for a in pool.speak_many(burst)
                      ) / synth.audio_output_info().sample_rate
        elapsed = time.perf_counter() - t0
        view = pool.stats_view()
        print(json.dumps({
            "metric": "replica_pool_audio_s_per_s",
            "value": round(audio_s / elapsed, 2),
            "unit": "audio_seconds_per_second",
            "vs_baseline": None,
            "replicas": len(pool.replicas),
            "devices": [str(r.device) for r in pool.replicas],
            "pool": {k: view[k] for k in ("routed", "dispatches",
                                          "healthy_replicas")},
        }))
    finally:
        pool.shutdown()


if __name__ == "__main__":
    main()
