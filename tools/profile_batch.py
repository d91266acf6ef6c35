"""Break down where batched-synthesis wall time goes on the live chip.

Separates, for the bench paragraph's single dispatch:
- enqueue time (host → async dispatch returns)
- device compute time (block_until_ready on the device outputs)
- result transfer time (device_get of the int16 wav + sidecars)

Run:  python tools/profile_batch.py
"""

from __future__ import annotations

import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import numpy as np

from bench import PARAGRAPH


def main() -> None:
    from sonata_tpu.models import PiperVoice
    from sonata_tpu.synth import SpeechSynthesizer

    voice = PiperVoice.random(seed=0, audio={"sample_rate": 22050,
                                             "quality": "high"})
    synth = SpeechSynthesizer(voice)
    phonemes = list(synth.phonemize_text(PARAGRAPH))
    print(f"platform={jax.devices()[0].platform} "
          f"sentences={len(phonemes)}")

    # warmup like bench.py
    for _ in range(6):
        n = len(voice._full_cache)
        voice.speak_batch(phonemes)
        if len(voice._full_cache) == n:
            break

    sc = voice.get_fallback_synthesis_config()
    ids_list = [voice.config.phonemes_to_ids(p) for p in phonemes]
    ids, lens, b, t = voice._pad_batch(ids_list)
    nw, ls, ns, ls_host = voice._scale_arrays(sc, b)
    weighted = float(max(len(r) * max(ls_host[i], 0.05)
                         for i, r in enumerate(ids_list)))
    f = voice.frame_estimator.bucket(weighted)
    print(f"buckets: b={b} t={t} f={f} "
          f"(frames_per_id={voice.frame_estimator.frames_per_id:.2f})")
    fn = voice._full_fn(b, t, f)
    rng = voice._next_rng()
    args = [voice.params, ids, lens, rng, nw, ls, ns]

    n_bytes = b * f * 256 * 2
    print(f"wav transfer size: {n_bytes/1e6:.2f} MB "
          f"(b={b} x frames={f} x hop=256 x i16)")

    for i in range(4):
        t0 = time.perf_counter()
        out = fn(*args)
        t1 = time.perf_counter()
        jax.block_until_ready(out)
        t2 = time.perf_counter()
        host = jax.device_get(out)
        t3 = time.perf_counter()
        print(f"iter{i}: enqueue={1e3*(t1-t0):7.1f}ms "
              f"compute={1e3*(t2-t1):7.1f}ms "
              f"transfer={1e3*(t3-t2):7.1f}ms "
              f"total={1e3*(t3-t0):7.1f}ms")

    # end-to-end comparison (includes python pack/unpack)
    t0 = time.perf_counter()
    audios = voice.speak_batch(phonemes)
    t1 = time.perf_counter()
    dur = sum(a.duration_ms() for a in audios) / 1000.0
    print(f"speak_batch e2e: {1e3*(t1-t0):.1f}ms for {dur:.1f}s audio "
          f"→ RTF {(t1-t0)/dur:.5f}")

    # how much of the frame bucket is real audio?
    used = sum(len(a.samples) for a in audios)
    print(f"bucket utilization: {used}/{b*f*256} = {used/(b*f*256):.1%}")


if __name__ == "__main__":
    main()
