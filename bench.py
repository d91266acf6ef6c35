"""Benchmark: flagship Piper voice RTF on the platform JAX selects.

Prints ONE JSON line:
  {"metric": ..., "value": N, "unit": ..., "vs_baseline": N,
   "platform": ..., "device_kind": ..., "device_count": N}

Metric: aggregate real-time factor (inference seconds per second of audio)
for batched synthesis of a fixed paragraph with the en_US-lessac-high
architecture (hidden 192, HiFi-GAN 512→[8,8,2,2], 22.05 kHz — randomly
initialized: no voice files ship with this environment, and RTF depends on
the graph, not the weight values).

``vs_baseline``: the reference publishes no numbers (BASELINE.md), so the
driver's north-star target — RTF < 0.01 — is the baseline; values > 1.0
mean faster than target.
"""

from __future__ import annotations

import json
import time

TARGET_RTF = 0.01

PARAGRAPH = (
    "The quick brown fox jumps over the lazy dog near the river bank. "
    "Speech synthesis turns written language into audible sound waves. "
    "Modern accelerators compile the whole network into one program. "
    "Each sentence becomes a batch row padded to a fixed bucket length. "
    "The decoder upsamples latent frames into waveform samples. "
    "Streaming mode trades throughput for time to first byte. "
    "Benchmarks should measure steady state after warmup compilation. "
    "Large batches amortize dispatch latency across many sentences. "
    "A narrator reads one sentence while the next is already queued. "
    "Quantized samples travel back to the host as compact integers. "
    "Every audio frame expands into two hundred fifty six samples. "
    "The encoder walks the phoneme sequence with windowed attention. "
    "A normalizing flow turns simple noise into rich acoustic detail. "
    "The duration predictor decides how long each phoneme should last. "
    "Parallel chips can each synthesize their own slice of the batch. "
    "This paragraph has exactly sixteen sentences for the batch."
)


def device_summary() -> dict:
    """The device JAX selected, as every result line names it.  A backend
    that does not initialize is JAX's own uncaught error: this script
    runs on the platform JAX picks and never falls back."""
    import jax

    devices = jax.devices()
    return {"platform": devices[0].platform,
            "device_kind": devices[0].device_kind,
            "device_count": len(devices)}


def main() -> None:
    import os

    import jax

    # persistent executable cache: repeat bench runs (and the driver's)
    # skip the cold compile of the full model
    from sonata_tpu.utils.jax_cache import enable_persistent_compile_cache

    enable_persistent_compile_cache()
    device = device_summary()

    from sonata_tpu.models import PiperVoice
    from sonata_tpu.synth import SpeechSynthesizer

    voice = PiperVoice.random(seed=0, audio={"sample_rate": 22050,
                                             "quality": "high"})
    synth = SpeechSynthesizer(voice)
    phonemes = list(synth.phonemize_text(PARAGRAPH))

    # warmup until the executable caches stop growing: each run draws fresh
    # duration noise, so neighboring frame buckets may compile on runs 2-3 —
    # those compiles must not land inside the timed loop
    audio_seconds = 0.0
    for _ in range(6):
        n_compiled = len(voice._full_cache)
        warm = voice.speak_batch(phonemes)
        audio_seconds = sum(a.duration_ms() for a in warm) / 1000.0
        if len(voice._full_cache) == n_compiled:
            break

    # the frame-bucket estimate rides the duration draw, so a run can land
    # one bucket up or down from the warmed ones — prewarm each cached
    # shape's neighbors so no compile can fall inside the timed loop,
    # here or in the driver's single run
    voice.prewarm_neighbor_buckets()

    iters = int(os.environ.get("SONATA_BENCH_ITERS", "5"))
    total_audio = 0.0
    profile_dir = os.environ.get("SONATA_PROFILE")  # xprof trace target
    import contextlib

    ctx = (jax.profiler.trace(profile_dir) if profile_dir
           else contextlib.nullcontext())
    with ctx:
        t0 = time.perf_counter()
        for _ in range(iters):
            audios = voice.speak_batch(phonemes)
            total_audio += sum(a.duration_ms() for a in audios) / 1000.0
        elapsed = time.perf_counter() - t0
    rtf = elapsed / max(total_audio, 1e-9)

    print(json.dumps({
        "metric": "piper_lessac_high_batch_rtf",
        "value": round(rtf, 6),
        "unit": "s_inference_per_s_audio",
        "vs_baseline": round(TARGET_RTF / rtf, 3),
        **device,
    }))
    # context for humans reading the log (driver parses the line above)
    import sys

    print(f"# {len(phonemes)} sentences, {audio_seconds:.1f}s audio/iter, "
          f"{iters} iters, {elapsed:.2f}s wall, "
          f"audio-s/s = {1.0 / rtf:.1f}", file=sys.stderr)


if __name__ == "__main__":
    main()
