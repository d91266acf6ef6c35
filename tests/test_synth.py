"""Synthesizer-layer tests: stream modes, prosody post-processing, native
DSP vs numpy fallback parity.

Replaces the reference's non-hermetic tier-3 tests
(``crates/sonata/synth/src/tests.rs`` — lazy/parallel/realtime drain against
developer-downloaded voices) with the same three drains against a hermetic
tiny voice, plus golden-metric checks on the DSP the reference never had.
"""

import numpy as np
import pytest

from sonata_tpu.audio import AudioSamples, read_wave_file
from sonata_tpu.synth import (
    AudioOutputConfig,
    SpeechSynthesizer,
    percent_to_param,
)
from sonata_tpu.synth.output import (
    _process_numpy,
    process_prosody,
)
from sonata_tpu.native import load_dsp_library

from voices import tiny_voice

TEXT = "Hello world. This is a test of the synthesizer layer."


@pytest.fixture(scope="module")
def synth():
    return SpeechSynthesizer(tiny_voice())


# ---------------------------------------------------------------------------
# stream modes (reference tests.rs:1-28, hermetic here)
# ---------------------------------------------------------------------------

def test_lazy_stream_drains(synth):
    audios = list(synth.synthesize_lazy(TEXT))
    assert len(audios) == 2
    assert all(len(a.samples) > 0 for a in audios)


def test_batched_stream_drains(synth):
    audios = list(synth.synthesize_parallel(TEXT))
    assert len(audios) == 2
    assert all(np.isfinite(a.samples.data).all() for a in audios)


def test_realtime_stream_drains(synth):
    chunks = list(synth.synthesize_streamed(TEXT, chunk_size=15,
                                            chunk_padding=2))
    assert len(chunks) >= 2
    assert all(len(c.samples) > 0 for c in chunks)


def test_realtime_stream_legacy_model_signature_with_deadline():
    """Review-pass pin: a model still implementing the pre-PR-10
    3-parameter ``stream_synthesis(phonemes, chunk, padding)`` protocol
    keeps serving realtime streams even when the caller sets a deadline
    (the deadline is dropped for legacy models; the frontends' own
    between-chunk checks still bound the request)."""
    import numpy as np

    from sonata_tpu.audio import Audio, AudioSamples
    from sonata_tpu.core import AudioInfo, Phonemes
    from sonata_tpu.serving import Deadline

    class Legacy:
        def phonemize_text(self, text):
            return Phonemes(["x"])

        def supports_streaming_output(self):
            return True

        def stream_synthesis(self, phonemes, chunk_size, chunk_padding):
            yield Audio(AudioSamples(np.zeros(64, dtype=np.float32)),
                        AudioInfo(sample_rate=16000), inference_ms=0.1)

        def audio_output_info(self):
            return AudioInfo(sample_rate=16000)

    s = SpeechSynthesizer(Legacy())
    chunks = list(s.synthesize_streamed("hi",
                                        deadline=Deadline.after(30)))
    assert len(chunks) == 1 and len(chunks[0].samples) == 64
    # and without a deadline the legacy call shape is untouched
    chunks = list(s.synthesize_streamed("hi"))
    assert len(chunks) == 1


def test_realtime_stream_forwards_errors():
    from sonata_tpu.core import OperationError

    class Boom:
        def phonemize_text(self, text):
            from sonata_tpu.core import Phonemes

            return Phonemes(["x"])

        def supports_streaming_output(self):
            return True

        def stream_synthesis(self, *a):
            raise OperationError("boom")

        def audio_output_info(self):
            raise NotImplementedError

    s = SpeechSynthesizer(Boom())
    stream = s.synthesize_streamed("hi")
    with pytest.raises(OperationError, match="boom"):
        list(stream)


def test_synthesize_to_file(tmp_path, synth):
    path = tmp_path / "out.wav"
    synth.synthesize_to_file(path, TEXT)
    samples, sr, _ = read_wave_file(path)
    assert sr == synth.audio_output_info().sample_rate
    assert len(samples) > 100


# ---------------------------------------------------------------------------
# prosody / output config
# ---------------------------------------------------------------------------

def test_percent_to_param_ranges():
    # synth/utils.rs:6-8 semantics over lib.rs:13-15 ranges
    assert percent_to_param(0, 0.5, 5.5) == pytest.approx(0.5)
    assert percent_to_param(100, 0.5, 5.5) == pytest.approx(5.5)
    assert percent_to_param(50, 0.0, 1.0) == pytest.approx(0.5)
    assert percent_to_param(50, 0.5, 1.5) == pytest.approx(1.0)


def _tone(sr=16000, ms=400, hz=220):
    t = np.arange(int(sr * ms / 1000)) / sr
    return (0.5 * np.sin(2 * np.pi * hz * t)).astype(np.float32)


def test_rate_changes_duration():
    sr = 16000
    x = _tone(sr)
    fast = process_prosody(x, sr, speed=2.0)
    slow = process_prosody(x, sr, speed=0.5)
    assert len(fast) == pytest.approx(len(x) / 2, rel=0.1)
    assert len(slow) == pytest.approx(len(x) * 2, rel=0.1)


def test_pitch_preserves_duration_and_shifts_frequency():
    sr = 16000
    x = _tone(sr, hz=220)
    up = process_prosody(x, sr, pitch=1.5)
    assert len(up) == pytest.approx(len(x), rel=0.1)
    # dominant frequency moves up by ~1.5x
    def peak_hz(sig):
        spec = np.abs(np.fft.rfft(sig * np.hanning(len(sig))))
        return np.argmax(spec) * sr / len(sig)
    assert peak_hz(up) == pytest.approx(peak_hz(x) * 1.5, rel=0.15)


def test_volume_scales_amplitude():
    sr = 16000
    x = _tone(sr)
    quiet = process_prosody(x, sr, volume=0.25)
    assert np.max(np.abs(quiet)) == pytest.approx(0.125, rel=0.05)


def test_appended_silence_before_rate():
    sr = 16000
    cfg = AudioOutputConfig(rate=50, appended_silence_ms=100)  # rate 50 → 3x
    out = cfg.apply(AudioSamples(_tone(sr, ms=300)), sr)
    # (300ms + 100ms silence) / 3 ≈ 133ms
    assert len(out) == pytest.approx(sr * 0.4 / 3.0, rel=0.15)


def test_native_dsp_available_and_matches_fallback():
    lib = load_dsp_library()
    assert lib is not None, "C++ DSP library failed to build"
    sr = 16000
    x = _tone(sr, ms=250)
    native = process_prosody(x, sr, speed=1.7, pitch=1.2, volume=0.8)
    fallback = _process_numpy(x, sr, 1.7, 1.2, 0.8)
    # same algorithm, so closely matching length and energy
    assert len(native) == pytest.approx(len(fallback), abs=max(
        8, 0.02 * len(fallback)))
    rms_n = np.sqrt(np.mean(native ** 2))
    rms_f = np.sqrt(np.mean(fallback ** 2))
    assert rms_n == pytest.approx(rms_f, rel=0.2)


def test_noop_config_is_identity():
    x = _tone()
    out = AudioOutputConfig().apply(AudioSamples(x), 16000)
    np.testing.assert_array_equal(out.data, x)


def test_batch_scheduler_coalesces_concurrent_requests():
    import concurrent.futures as cf

    from sonata_tpu.synth import BatchScheduler

    voice = tiny_voice(seed=9)
    dispatches = []
    real = voice.speak_batch

    def counting(sentences, speakers=None, scales=None):
        dispatches.append(len(sentences))
        return real(sentences, speakers=speakers, scales=scales)

    voice.speak_batch = counting
    sched = BatchScheduler(voice, max_batch=8, max_wait_ms=200.0)
    try:
        # warm the jit caches so the first dispatch doesn't hog the worker
        real(["wɔːm ʌp."])
        with cf.ThreadPoolExecutor(8) as ex:
            audios = list(ex.map(
                lambda i: sched.speak(f"tɛst nʌmbɚ {i}."), range(8)))
        assert all(len(a.samples) > 0 for a in audios)
        # 8 concurrent requests must land in far fewer dispatches
        assert len(dispatches) < 8
        assert sum(dispatches) == 8
    finally:
        sched.shutdown()


def test_batch_scheduler_propagates_errors():
    from sonata_tpu.core import OperationError
    from sonata_tpu.synth import BatchScheduler

    class Bad:
        def speak_batch(self, sentences, speakers=None, scales=None):
            raise OperationError("device on fire")

    sched = BatchScheduler(Bad(), max_wait_ms=1.0)
    try:
        with pytest.raises(OperationError, match="device on fire"):
            sched.speak("x")
    finally:
        sched.shutdown()


def test_batch_scheduler_rejects_after_shutdown():
    from sonata_tpu.core import OperationError
    from sonata_tpu.synth import BatchScheduler

    voice = tiny_voice(seed=9)
    sched = BatchScheduler(voice)
    sched.shutdown()
    with pytest.raises(OperationError):
        sched.submit("x")


def test_batch_scheduler_shutdown_fails_pending():
    from sonata_tpu.core import OperationError
    from sonata_tpu.synth import BatchScheduler

    import threading

    release = threading.Event()

    class Slow:
        def speak_batch(self, sentences, speakers=None, scales=None):
            release.wait(5.0)
            raise OperationError("never mind")

    sched = BatchScheduler(Slow(), max_wait_ms=1.0)
    first = sched.submit("occupies the worker")
    import time

    time.sleep(0.05)
    pending = sched.submit("stuck in queue")
    release.set()
    sched.shutdown()
    with pytest.raises(OperationError):
        pending.result(timeout=5.0)
    with pytest.raises(OperationError):
        first.result(timeout=5.0)


def test_batch_scheduler_survives_cancelled_future():
    from sonata_tpu.synth import BatchScheduler

    voice = tiny_voice(seed=9)
    voice.speak_batch(["wɔːm."])  # warm jit
    sched = BatchScheduler(voice, max_wait_ms=1.0)
    try:
        fut = sched.submit("tɛst wʌn.")
        fut.cancel()  # may race the worker; must not kill it
        ok = sched.speak("tɛst tuː.", timeout=30.0)
        assert len(ok.samples) > 0  # worker still alive
    finally:
        sched.shutdown()


def test_stream_normalization_modes(synth):
    """Default replicates the reference's per-chunk peak normalization;
    stream_normalization="global" applies one fixed unit-range gain so
    chunks cannot seam (PARITY.md ADR)."""
    cfg = AudioOutputConfig(stream_normalization="global")
    fixed = list(synth.synthesize_streamed(TEXT, cfg, chunk_size=15,
                                           chunk_padding=2))
    default = list(synth.synthesize_streamed(TEXT, chunk_size=15,
                                             chunk_padding=2))
    assert fixed and default
    for chunk in fixed:
        i16 = chunk.samples.to_i16()
        expect = np.clip(chunk.samples.data * 32767.0,
                         -32768.0, 32767.0).astype(np.int16)
        np.testing.assert_array_equal(i16, expect)  # one fixed gain
    # per-chunk default: every non-silent chunk's loudest sample hits
    # full scale regardless of its true amplitude
    for chunk in default:
        peak = float(np.max(np.abs(chunk.samples.data)))
        if peak > 0.01:
            assert int(np.max(np.abs(chunk.samples.to_i16()))) >= 32700


# ---------------------------------------------------------------------------
# concurrent realtime streams coalesce through the shared decoder
# (VERDICT round-1 next#7; reference gap: grpc/src/main.rs:381-409)
# ---------------------------------------------------------------------------

def test_stream_decode_coalescer_correctness():
    """A window decoded through the coalescer (possibly batched with
    other streams' windows) equals the direct single-stream decode."""
    import jax
    import jax.numpy as jnp
    from concurrent.futures import wait

    from sonata_tpu.synth.stream_engines import _StreamDecodeCoalescer

    v = tiny_voice(seed=9)
    # wide wait window so the 4 submissions deterministically coalesce
    # even on a loaded 1-core host
    v._stream_coalescer = _StreamDecodeCoalescer(v, max_wait_ms=300.0)
    f = 64
    z = jax.random.normal(jax.random.PRNGKey(3),
                          (1, f, v.hp.inter_channels))
    width = 16
    direct = np.asarray(v._decode_window_fn(width)(v.params, z, 8))[0]
    # submit 4 equal-shape requests at once so they coalesce
    futs = [v._stream_decoder.submit(z[0], 8, width, None)
            for _ in range(4)]
    wait(futs)
    for fut in futs:
        np.testing.assert_allclose(fut.result(), direct, atol=1e-5)
    stats = v._stream_decoder.stats
    assert stats["dispatches"] < stats["requests"]  # they actually batched


def test_concurrent_streams_share_dispatches():
    import threading

    from sonata_tpu.synth.stream_engines import _StreamDecodeCoalescer

    v = tiny_voice(seed=5)
    # wide wait window: on a loaded 1-core host the four stream threads
    # can skew past a small window at every chunk wave, which would make
    # the batching assertion timing-dependent
    v._stream_coalescer = _StreamDecodeCoalescer(v, max_wait_ms=300.0)
    results = [None] * 4

    def run(i):
        chunks = list(v.stream_synthesis("tɛst nʌmbɚ wˈʌn tuː θɹˈiː",
                                         12, 2))
        results[i] = np.concatenate([c.samples.data for c in chunks])

    threads = [threading.Thread(target=run, args=(i,)) for i in range(4)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert all(r is not None and len(r) > 0 for r in results)
    stats = v._stream_coalescer.stats
    assert stats["dispatches"] < stats["requests"]


def test_stream_stage_coalescer_batches_starts():
    """Concurrent stream STARTS share one encode+acoustics dispatch, pad
    to the canonical max batch, and still return per-stream latents that
    drive correct chunk synthesis (round-2: stage coalescing)."""
    import threading

    from sonata_tpu.synth.stream_engines import _StreamStageCoalescer

    v = tiny_voice(seed=7)
    v._stage_coalescer = _StreamStageCoalescer(v, max_wait_ms=300.0)
    sc = v.get_fallback_synthesis_config()
    ids = v.config.phonemes_to_ids("həlˈoʊ wˈɜːld")
    results = [None] * 3

    def run(i):
        results[i] = v._stream_stages.start(list(ids), sc)

    threads = [threading.Thread(target=run, args=(i,)) for i in range(3)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    for z_row, total_frames, f, sid0 in results:
        assert z_row.shape[0] == f and z_row.shape[1] == v.hp.inter_channels
        assert 0 < total_frames
        assert sid0 is None  # single-speaker tiny voice
    stats = v._stage_coalescer.stats
    assert stats["dispatches"] < stats["requests"]
    # the multi-stream group padded to the canonical batch: only the
    # (1, t) and (max_batch, t) encode shapes may exist
    enc_bs = {b for (b, _t) in v._enc_cache}
    assert enc_bs <= {1, v._stage_coalescer._max_batch}


def test_concurrent_streams_full_path_via_stage_coalescer():
    """End-to-end: concurrent stream_synthesis calls complete and produce
    audio with the stage coalescer active (default path)."""
    import threading

    v = tiny_voice(seed=11)
    results = [None] * 3

    def run(i):
        chunks = list(v.stream_synthesis("wˈʌn tuː θɹˈiː fˈoːɹ", 12, 2))
        results[i] = np.concatenate([c.samples.data for c in chunks])

    threads = [threading.Thread(target=run, args=(i,)) for i in range(3)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert all(r is not None and len(r) > 0 for r in results)


def test_speak_batch_per_dispatch_timing():
    """Per-row inference_ms reflects the dispatch that produced the row
    (reference times each session.run — piper/src/lib.rs:361-380): rows
    sharing a dispatch group share one measured wall time; rows in
    different groups carry different measurements — not one whole-batch
    average fabricated uniformly."""
    voice = tiny_voice()
    short = ["wʌn.", "tuː.", "θɹiː."]
    # a text-bucket jump past 2x forces a second dispatch group
    long_ipa = ("ðɪs ɪz ə mʌtʃ lɔːŋɡɚ sɛntəns wɪθ mɛni mɔːɹ foʊniːmz "
                "ðæn ðə ʃɔːɹt wʌnz səʊ ɪt lændz ɪn ə fɑːɹ lɑːɹdʒɚ "
                "tɛkst bʌkɪt ænd ɡɛts ɪts oʊn dɪspætʃ.")
    audios = voice.speak_batch(short + [long_ipa])
    ms = [a.inference_ms for a in audios]
    assert all(m > 0 for m in ms)
    # the three short rows rode one dispatch: identical measured time
    assert ms[0] == ms[1] == ms[2]
    # the long row rode its own dispatch: its own measured time
    assert ms[3] != ms[0]


def test_prewarm_invariant_no_cold_compiles():
    """THE property prewarm exists for: after prewarm(streaming=True), a
    concurrent 8-stream burst plus a batched wave trigger ZERO new
    executable-cache entries — warm-path serving never pays a mid-request
    XLA compile (VERDICT r2 next#4)."""
    import threading

    v = tiny_voice(seed=21)
    v.prewarm(streaming=True, chunk_size=12, chunk_padding=2)

    def cache_keys():
        # dict keys plus each jitted fn's internal shape-specialization
        # count: a new (batch, text) shape through a cached fn is a cold
        # compile the outer dicts cannot see
        def sizes(d):
            return {k: getattr(fn, "_cache_size", lambda: -1)()
                    for k, fn in d.items()}

        return (sizes(v._full_cache), sizes(v._enc_cache),
                sizes(v._aco_cache), sizes(v._dec_cache))

    warmed = cache_keys()

    # burst texts come from the prewarm set: that is the coverage prewarm
    # promises (traffic in never-warmed text buckets legitimately compiles)
    burst = list(v.phonemize_text(v._PREWARM_TEXTS[1]))[0]
    results = [None] * 8

    def run(i):
        chunks = list(v.stream_synthesis(burst, 12, 2))
        results[i] = np.concatenate([c.samples.data for c in chunks])

    threads = [threading.Thread(target=run, args=(i,)) for i in range(8)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert all(r is not None and len(r) > 0 for r in results)
    # plus a batched wave over the same prewarm texts
    phonemes = [p for t in v._PREWARM_TEXTS for p in v.phonemize_text(t)]
    v.speak_batch(phonemes)
    after = cache_keys()
    grown = [{k: s for k, s in a.items() if w.get(k) != s}
             for w, a in zip(warmed, after)]
    assert after == warmed, f"cold compiles after prewarm: {grown}"


def test_voice_close_stops_coalescer_threads():
    """close() tears down all four sonata_stream_*/stage threads and is
    idempotent; queued-but-undispatched work fails instead of hanging
    (VERDICT r2 next#6)."""
    import threading

    v = tiny_voice(seed=22)
    list(v.stream_synthesis("wˈʌn tuː.", 12, 2))  # spawn the threads
    own = [v._stream_coalescer._worker, v._stream_coalescer._finisher,
           v._stage_coalescer._worker, v._stage_coalescer._finisher]
    assert all(t.is_alive() for t in own)
    v.close()
    v.close()  # idempotent
    lingering = [t.name for t in own if t.is_alive()]
    assert not lingering, f"lingering threads: {lingering}"
    # non-streaming synthesis still works on a closed voice
    assert len(v.speak_batch(["tɛst."])[0].samples) > 0


def test_voice_close_is_terminal_for_streaming():
    """After close(), streaming raises OperationError instead of lazily
    respawning coalescer threads (advisor r3: close() was not terminal —
    the lazy properties resurrected fresh daemon threads on next
    access, contradicting UnloadVoice's in-flight-failure contract)."""
    import pytest

    from sonata_tpu.core import OperationError

    v = tiny_voice(seed=23)
    list(v.stream_synthesis("wˈʌn.", 12, 2))
    v.close()
    with pytest.raises(OperationError):
        list(v.stream_synthesis("tuː.", 12, 2))
    # the coalescer slots stay None — the lazy properties must not have
    # rebuilt them (thread idents are reused after join, so slot identity
    # is the reliable respawn signal, not a thread-id diff)
    assert v._stream_coalescer is None and v._stage_coalescer is None


def test_coalescer_submit_after_close_fails_fast():
    """submit()/start() on a closed coalescer fail immediately with
    OperationError — no future is ever left unresolved for a caller
    blocked in fut.result() (advisor r3 medium finding)."""
    import jax.numpy as jnp
    import pytest

    from sonata_tpu.core import OperationError
    from sonata_tpu.models.config import SynthesisConfig

    v = tiny_voice(seed=24)
    list(v.stream_synthesis("wˈʌn.", 12, 2))  # materialize coalescers
    decoder, stages = v._stream_coalescer, v._stage_coalescer
    v.close()
    z = jnp.zeros((16, v.hp.inter_channels), dtype=jnp.float32)
    fut = decoder.submit(z, 0, 8, None)
    assert isinstance(fut.exception(timeout=5), OperationError)
    with pytest.raises(OperationError):
        stages.start([1, 2, 3], SynthesisConfig())


def test_coalescer_close_fails_queued_futures():
    """Work sitting in a coalescer queue when it closes gets an
    OperationError instead of leaving callers blocked forever on
    fut.result() (advisor r2 finding)."""
    import queue as _queue
    from concurrent.futures import Future

    from sonata_tpu.core import OperationError
    from sonata_tpu.synth.batching import drain_pending_futures

    q: "_queue.Queue" = _queue.Queue()
    f1, f2 = Future(), Future()
    q.put(("win", 16, None, f1))
    q.put(None)  # sentinel must be skipped
    q.put(("win", 16, None, f2))
    drain_pending_futures(q, lambda it: it[3], "closed in test")
    for f in (f1, f2):
        assert isinstance(f.exception(timeout=0), OperationError)
    # list-of-futures extraction (the stage-results layout)
    q2: "_queue.Queue" = _queue.Queue()
    f3, f4 = Future(), Future()
    q2.put(([("ids", None, f3), ("ids", None, f4)], "z"))
    drain_pending_futures(q2, lambda it: [g[2] for g in it[0]],
                           "closed in test")
    assert isinstance(f3.exception(timeout=0), OperationError)
    assert isinstance(f4.exception(timeout=0), OperationError)


def test_stream_synthesis_bounded_lookahead():
    """stream_synthesis keeps at most LOOKAHEAD window decodes in flight:
    an abandoned stream (client cancel) wastes bounded device work instead
    of decoding its whole tail (advisor r2 finding)."""
    v = tiny_voice(seed=23)
    # long utterance → many small windows
    phonemes = "ðɪs ɪz ə lˈɔːŋ ˈʌtɚɹəns wɪθ mˈɛni wˈɪndoʊz " * 3
    co = v._stream_decoder
    submitted = []
    real_submit = co.submit

    def counting_submit(*a, **kw):
        fut = real_submit(*a, **kw)
        submitted.append(fut)
        return fut

    co.submit = counting_submit
    try:
        gen = v.stream_synthesis(phonemes, 8, 2)
        first = next(gen)
        assert len(first.samples) > 0
        # first pull: initial look-ahead plus at most one top-up
        assert len(submitted) <= 4
        gen.close()  # abandon the stream
        n_after_close = len(submitted)
    finally:
        co.submit = real_submit
    assert n_after_close <= 4  # no tail decodes after abandonment
