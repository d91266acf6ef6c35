"""Decoder-arm parity gates: fused epilogue + int8 weight-only quant.

Every precision/fusion arm (``SONATA_FUSED_EPILOGUE=lax``,
``SONATA_DECODE_QUANT=int8``, and the pre-existing bf16 arm pinned in
test_vits_model.py) must stay within a measured distance of the float32
reference before its bench row means anything — the parity thresholds
here gate the arms the ISSUE-11 bench artifact reports:

- fused arm: the device epilogue (crossfade taper + peak-scaled i16
  quantize) must reproduce the host epilogue to i16-grid precision;
- int8 arm: weight-only quantization of the HiFi-GAN decoder convs must
  hold both waveform SNR above the repo's established reduced-precision
  bar (25 dB, the bf16 gate in test_vits_model.py) and log-spectral
  distance under 1 dB against f32.
"""

from __future__ import annotations

import numpy as np
import pytest

from sonata_tpu.core import OperationError
from sonata_tpu.models import decode_opts
from sonata_tpu.models.decode_opts import (
    DECODE_QUANT_ENV,
    FUSED_EPILOGUE_ENV,
    decoder_is_quantized,
    dequantize_chunk,
    dequantize_decoder,
    quantize_decoder,
    resolve_decode_quant,
    resolve_fused_epilogue,
)

from voices import tiny_voice

PHRASE = "ðɪs ɪz ə tɛst sɛntəns."
LONG_PHRASE = "ə lˈɔːŋɡɚ tɛst sɛntəns wɪθ mˈɛni wˈɪndoʊz hɪɹ."


# ---------------------------------------------------------------------------
# knob resolution (single-module defaults; typos fail loudly)
# ---------------------------------------------------------------------------

def test_fused_epilogue_resolution():
    assert resolve_fused_epilogue(env={}) == "lax"  # the default arm
    for mode in ("lax", "off"):
        assert resolve_fused_epilogue(env={FUSED_EPILOGUE_ENV: mode}) \
            == mode
        assert resolve_fused_epilogue(mode) == mode
    # "pallas" was an arm once (deleted: it never compiled above batch 1)
    for typo in ("laxx", "pallas"):
        with pytest.raises(OperationError, match="SONATA_FUSED_EPILOGUE"):
            resolve_fused_epilogue(env={FUSED_EPILOGUE_ENV: typo})


def test_decode_quant_resolution():
    assert resolve_decode_quant(env={}) is None
    assert resolve_decode_quant(env={DECODE_QUANT_ENV: "off"}) is None
    assert resolve_decode_quant(env={DECODE_QUANT_ENV: "int8"}) == "int8"
    assert resolve_decode_quant("off") is None
    with pytest.raises(OperationError, match="SONATA_DECODE_QUANT"):
        resolve_decode_quant(env={DECODE_QUANT_ENV: "int4"})


# ---------------------------------------------------------------------------
# fused epilogue: device math == host math
# ---------------------------------------------------------------------------

def _host_epilogue(wav, lo, hi, fade):
    """The exact host-side reference: slice, then AudioSamples.crossfade."""
    from sonata_tpu.audio import AudioSamples

    s = AudioSamples(wav[lo:hi])
    s.crossfade(fade)
    return s.data


def test_lax_epilogue_matches_host_crossfade():
    """Random rows with varied slice bounds (incl. a slice shorter than
    the taper): dequantize(i16, peak)[lo:hi] must equal the host
    slice+crossfade to i16-grid precision."""
    rng = np.random.default_rng(7)
    s = 512
    wav = rng.standard_normal((4, s)).astype(np.float32) * 0.5
    lo = np.asarray([0, 13, 100, 40], np.int32)
    hi = np.asarray([512, 500, 130, 60], np.int32)  # row 2: L < 42
    import jax.numpy as jnp

    q, peak = decode_opts.fused_epilogue(
        jnp.asarray(wav), jnp.asarray(lo), jnp.asarray(hi), 42)
    q, peak = np.asarray(q), np.asarray(peak)
    for i in range(4):
        got = dequantize_chunk(q[i], peak[i])[lo[i]:hi[i]]
        want = _host_epilogue(wav[i], int(lo[i]), int(hi[i]), 42)
        assert got.shape == want.shape
        tol = max(float(peak[i]), 0.01) / 32767.0  # one i16 grid step
        assert np.abs(got - want).max() <= tol + 1e-7, i


def _stream_audio(voice, phrase=LONG_PHRASE):
    chunks = list(voice.stream_synthesis(phrase, 12, 2))
    assert chunks
    return np.concatenate([c.samples.data for c in chunks])


def test_fused_lax_stream_parity_vs_off(monkeypatch):
    """End to end through the real streaming path: the fused-lax arm's
    audio equals the host-epilogue arm's within i16 quantization."""
    monkeypatch.setenv(FUSED_EPILOGUE_ENV, "off")
    v_off = tiny_voice(seed=21)
    a_off = _stream_audio(v_off)
    v_off.close()
    monkeypatch.setenv(FUSED_EPILOGUE_ENV, "lax")
    v_lax = tiny_voice(seed=21)
    assert v_lax.fused_epilogue == "lax"
    a_lax = _stream_audio(v_lax)
    v_lax.close()
    assert a_off.shape == a_lax.shape
    # one i16 grid step at the loudest plausible chunk peak
    assert np.abs(a_off - a_lax).max() < 2.0 / 32767.0


def test_fused_iteration_mode_stream_parity(monkeypatch):
    """The fused epilogue rides the iteration loop too (graduated-rung
    executables): same parity bar as the dispatch-mode path."""
    monkeypatch.setenv("SONATA_BATCH_MODE", "iteration")
    monkeypatch.setenv("SONATA_DISPATCH_POLICY", "on")
    monkeypatch.setenv(FUSED_EPILOGUE_ENV, "off")
    v_off = tiny_voice(seed=23)
    a_off = _stream_audio(v_off)
    v_off.close()
    monkeypatch.setenv(FUSED_EPILOGUE_ENV, "lax")
    v_lax = tiny_voice(seed=23)
    a_lax = _stream_audio(v_lax)
    v_lax.close()
    assert a_off.shape == a_lax.shape
    assert np.abs(a_off - a_lax).max() < 2.0 / 32767.0


# ---------------------------------------------------------------------------
# int8 weight-only decoder arm
# ---------------------------------------------------------------------------

def _snr_db(ref, x):
    ref = np.asarray(ref, np.float64)
    x = np.asarray(x, np.float64)
    err = x - ref
    denom = max(float((ref ** 2).mean()), 1e-12)
    return 10 * np.log10(denom / max(float((err ** 2).mean()), 1e-30))


def _log_spectral_distance_db(ref, x, nfft=512):
    """Mean log-magnitude spectral distance over frames (dB) — the
    spectral parity measure the precision arms gate on."""
    ref = np.asarray(ref, np.float64)
    x = np.asarray(x, np.float64)
    n = (min(len(ref), len(x)) // nfft) * nfft
    if n == 0:
        return 0.0
    r = np.fft.rfft(ref[:n].reshape(-1, nfft) * np.hanning(nfft), axis=1)
    y = np.fft.rfft(x[:n].reshape(-1, nfft) * np.hanning(nfft), axis=1)
    lr = 20 * np.log10(np.maximum(np.abs(r), 1e-8))
    ly = 20 * np.log10(np.maximum(np.abs(y), 1e-8))
    return float(np.sqrt(((lr - ly) ** 2).mean()))


def test_int8_decoder_parity_vs_f32(monkeypatch):
    """THE int8 gate: same voice, same seed, int8 decoder weights —
    waveform SNR above the repo's 25 dB reduced-precision bar (the bf16
    gate) and log-spectral distance under 1 dB."""
    ph = tiny_voice(seed=24).phonemize_text(
        "This sentence checks the quantized decoder.")
    a32 = tiny_voice(seed=24).speak_batch(ph)[0]
    monkeypatch.setenv(DECODE_QUANT_ENV, "int8")
    v8 = tiny_voice(seed=24)
    assert v8.decode_quant == "int8"
    assert decoder_is_quantized(v8.params["dec"])
    a8 = v8.speak_batch(ph)[0]
    assert len(a32.samples) == len(a8.samples)
    x32, x8 = a32.samples.data, a8.samples.data
    assert np.isfinite(x8).all()
    snr = _snr_db(x32, x8)
    assert snr > 25.0, f"int8 decode SNR too low: {snr:.1f} dB"
    lsd = _log_spectral_distance_db(x32, x8)
    assert lsd < 1.0, f"int8 spectral distance too high: {lsd:.2f} dB"


def test_int8_streaming_windows_finite(monkeypatch):
    """The window-decode caches carry the quantized weights too (both
    the fused and host-epilogue arms)."""
    monkeypatch.setenv(DECODE_QUANT_ENV, "int8")
    v = tiny_voice(seed=25)
    audio = _stream_audio(v, LONG_PHRASE)
    v.close()
    assert len(audio) > 0 and np.isfinite(audio).all()


def test_quantize_per_channel_properties():
    """Structural checks: int8 range, per-output-channel scales, exact
    idempotence, and a dequantization error bounded by half a scale
    step per weight."""
    rng = np.random.default_rng(3)
    pd = {"conv_pre": {"w": rng.standard_normal((7, 8, 16))
                       .astype(np.float32),
                       "b": np.zeros(16, np.float32)},
          "ups": [{"w": rng.standard_normal((16, 16, 8))
                   .astype(np.float32) * 3.0,
                   "b": np.zeros(8, np.float32)}]}
    q = quantize_decoder(pd)
    assert decoder_is_quantized(q) and not decoder_is_quantized(pd)
    assert q["conv_pre"]["w_q"].dtype == np.int8
    assert q["conv_pre"]["w_scale"].shape == (1, 1, 16)
    # idempotent: re-quantizing a quantized tree is a no-op (the
    # replica_for_device path hands back already-quantized params)
    q2 = quantize_decoder(q)
    assert q2["conv_pre"]["w_q"] is q["conv_pre"]["w_q"]
    dq = dequantize_decoder(q)
    for name in ("conv_pre",):
        w, w2 = pd[name]["w"], np.asarray(dq[name]["w"])
        step = np.abs(w).max(axis=(0, 1)) / 127.0
        assert np.all(np.abs(w - w2) <= step / 2 + 1e-7)
    # plain trees pass through dequantize untouched
    assert dequantize_decoder(pd) is pd


def test_int8_replica_shares_quantized_params(monkeypatch):
    """replica_for_device carries the arm: the device copy keeps the
    quantized decoder (no re-quantization, no silent f32 fallback)."""
    import jax

    monkeypatch.setenv(DECODE_QUANT_ENV, "int8")
    v = tiny_voice(seed=26)
    r = v.replica_for_device(jax.devices()[0])
    assert r.decode_quant == "int8"
    assert decoder_is_quantized(r.params["dec"])
    assert r.fused_epilogue == v.fused_epilogue
    r.close()
    v.close()


def test_int8_mesh_refused():
    from sonata_tpu.models.piper import PiperVoice

    v = tiny_voice(seed=27)
    with pytest.raises(OperationError, match="mesh"):
        PiperVoice(v.config, v.params, mesh=object(), decode_quant="int8")
    v.close()


def test_aot_key_distinguishes_quant(monkeypatch):
    """A quantized voice's AOT executables must never collide with the
    f32 blobs (different programs, same dims)."""
    v = tiny_voice(seed=28)
    k_f32 = v._aot_key((1, 16, 64))
    monkeypatch.setenv(DECODE_QUANT_ENV, "int8")
    v8 = tiny_voice(seed=28)
    assert v8._aot_key((1, 16, 64)) != k_f32
    v.close()
    v8.close()
