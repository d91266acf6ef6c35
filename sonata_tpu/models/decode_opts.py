"""Decoder-arm options: fused decode epilogue + int8 weight-only quant.

Two independent, env-gated speedups for the HiFi-GAN decode path, both
measured by ``tools/bench_cpu.py`` arms and parity-gated against float32
(tests/test_decode_opts.py):

**Fused decode epilogue** (``SONATA_FUSED_EPILOGUE=lax|off``,
default ``lax``): the streaming pipeline used to ship every decoded
window back to the host as float32 and run the per-chunk epilogue there
— slice to the emitted range, crossfade taper
(:data:`~sonata_tpu.models.chunker.CROSSFADE_SAMPLES`), i16 conversion
at output time.  That host work sits directly on TTFB and per-chunk
latency, and the f32 transfer is twice the bytes the audio needs.  The
fused arm runs taper + peak-scaled i16 quantization *inside the same
device program as the window decode* (one jitted executable per
(width, batch rung) — see ``PiperVoice._decode_windows_fused_fn``), so
one dispatch returns quantized, already-tapered samples plus the
per-row peak for exact host-side dequantization.  ``lax`` composes the
epilogue from jnp ops, which XLA fuses into the decode's last stage;
``off`` restores the host-side epilogue.

**int8 weight-only decoder quantization** (``SONATA_DECODE_QUANT=int8``,
default off): per-output-channel symmetric int8 quantization of every
decoder conv weight, dequantized *in kernel* (the int8 weights ship to
the device; the jitted program rescales them to f32/bf16 right before
each conv — activations keep full precision).  Quarters the decoder
weight HBM traffic; gated by the spectral-distance parity test against
f32.

This module is the single reader of both knobs (the sonata-lint knob
registry's split-default rule).
"""

from __future__ import annotations

import os
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..core import OperationError
from . import vits

# ---------------------------------------------------------------------------
# knob resolution (single-module defaults)
# ---------------------------------------------------------------------------

FUSED_EPILOGUE_ENV = "SONATA_FUSED_EPILOGUE"
FUSED_EPILOGUE_MODES = ("lax", "off")

DECODE_QUANT_ENV = "SONATA_DECODE_QUANT"


def resolve_fused_epilogue(setting: Optional[str] = None,
                           env: Optional[dict] = None) -> str:
    """``lax`` | ``off``; a typo fails loudly (the
    SONATA_BATCH_MODE contract: a fleet silently running the wrong
    epilogue arm is a perf regression nobody would see)."""
    if setting is None:
        env = os.environ if env is None else env
        setting = env.get(FUSED_EPILOGUE_ENV, "").strip().lower()
    if not setting:
        return "lax"
    if setting not in FUSED_EPILOGUE_MODES:
        raise OperationError(
            f"{FUSED_EPILOGUE_ENV}={setting!r} is not one of "
            f"{'/'.join(FUSED_EPILOGUE_MODES)}")
    return setting


def resolve_decode_quant(setting: Optional[str] = None,
                         env: Optional[dict] = None) -> Optional[str]:
    """``int8`` or None (off); a typo fails loudly."""
    if setting is None:
        env = os.environ if env is None else env
        setting = env.get(DECODE_QUANT_ENV, "").strip().lower()
    if setting in ("", "off", "0"):
        return None
    if setting == "int8":
        return "int8"
    raise OperationError(
        f"{DECODE_QUANT_ENV}={setting!r} is not one of int8/off")


# ---------------------------------------------------------------------------
# fused epilogue: crossfade taper + peak-scaled i16 quantize, on device
# ---------------------------------------------------------------------------

def _taper_gains(idx, lo, hi, fade: int):
    """Per-sample gain replicating the host epilogue exactly: quarter-sine
    fade-in over the first ``min(fade, L)`` samples of the emitted range
    [lo, hi), quarter-cosine fade-out over the last — both applied
    (multiplicatively, like ``AudioSamples.crossfade``) when the range is
    shorter than ``2*fade`` — and zero outside the range (the host
    slices it away; zeroing makes the masked peak exact)."""
    length = hi - lo
    n = jnp.minimum(jnp.int32(fade), length)
    nf = jnp.maximum(n, 1).astype(jnp.float32)
    half_pi = jnp.float32(np.pi / 2)
    j = (idx - lo).astype(jnp.float32)
    k = (idx - (hi - n)).astype(jnp.float32)
    in_gain = jnp.where(idx - lo < n, jnp.sin(j / nf * half_pi), 1.0)
    out_gain = jnp.where(idx >= hi - n, jnp.cos(k / nf * half_pi), 1.0)
    mask = ((idx >= lo) & (idx < hi)).astype(jnp.float32)
    return in_gain * out_gain * mask


def _quantize_rows(tapered):
    """Peak-scaled i16, the :func:`decode_quantize` contract: per-row peak
    ships back so the host restores original amplitudes exactly (modulo
    the i16 grid), with the same 0.01 silence floor."""
    peak = jnp.max(jnp.abs(tapered), axis=-1)
    scale = 32767.0 / jnp.maximum(peak, 0.01)[..., None]
    q = jnp.clip(tapered * scale, -32768.0, 32767.0).astype(jnp.int16)
    return q, peak


def fused_epilogue(wav, lo, hi, fade: int):
    """Crossfade taper + peak-scaled i16 quantize, composed from jnp ops
    inside the decode's device program.

    ``wav``: [B, S] float32 decoded windows; ``lo``/``hi``: [B] int32
    sample bounds of each row's emitted slice.  Returns
    (i16 [B, S], peak [B])."""
    idx = jnp.arange(wav.shape[-1], dtype=jnp.int32)[None, :]
    gains = _taper_gains(idx, lo[:, None], hi[:, None], fade)
    return _quantize_rows(wav * gains)


def dequantize_chunk(q, peak):
    """Host-side inverse of the fused quantize for one row: restores the
    pre-quantization float32 amplitudes (the exact ``_finish_batch``
    dequantization contract, same 0.01 floor)."""
    return np.asarray(q, np.float32) * (max(float(peak), 0.01) / 32767.0)


def decode_quantize(params, hp, z, y_lengths, g, mesh=None,
                    compute_dtype=None):
    """HiFi-GAN decode + on-device peak-scaled i16 quantization.

    i16 quarters the host transfer, which dominates when the chip sits
    behind a network link.  The per-row peak ships back too so the host
    restores original amplitudes: relative loudness across sentences is
    preserved, and the final WAV write still applies the reference's
    single global normalization (samples.rs:51-75).

    The single definition of the quantization contract: every program of
    any voice family that decodes whole rows goes through here.
    """
    wav = vits.decode(params, hp, z, g=g, mesh=mesh,
                      compute_dtype=compute_dtype)
    with jax.named_scope("epilogue"):
        wav_lengths = y_lengths * hp.hop_length
        valid = (jnp.arange(wav.shape[1])[None, :]
                 < wav_lengths[:, None])
        peak = jnp.max(jnp.abs(wav) * valid, axis=1, keepdims=True)
        scale = 32767.0 / jnp.maximum(peak, 0.01)
        wav_i16 = jnp.clip(wav * scale, -32768.0,
                           32767.0).astype(jnp.int16)
    return wav_i16, wav_lengths, peak[:, 0]


# ---------------------------------------------------------------------------
# int8 weight-only decoder quantization
# ---------------------------------------------------------------------------

def _map_convs(tree, fn):
    """Apply ``fn`` to every conv-param dict (the {w, b} /
    {w_q, w_scale, b} leaves) of a decoder subtree, preserving
    structure."""
    if isinstance(tree, dict):
        if "w" in tree or "w_q" in tree:
            return fn(tree)
        return {k: _map_convs(v, fn) for k, v in tree.items()}
    if isinstance(tree, (list, tuple)):
        return [_map_convs(v, fn) for v in tree]
    return tree


def quantize_decoder(pd):
    """Per-output-channel symmetric int8 of every decoder conv weight.

    Weights are stored [K, C_in, C_out]; each output channel gets its
    own scale (``max|w| / 127`` over the kernel and input axes), so a
    quiet channel is not crushed by a loud one's range.  Biases stay
    float32 (tiny, and additive error does not amortize).  Host-side
    numpy, once, at voice load."""
    def q_conv(p):
        if "w_q" in p:
            return p  # already quantized (replica copies)
        w = np.asarray(p["w"], np.float32)
        scale = np.max(np.abs(w), axis=tuple(range(w.ndim - 1)),
                       keepdims=True) / 127.0
        scale = np.maximum(scale, 1e-12).astype(np.float32)
        wq = np.clip(np.rint(w / scale), -127, 127).astype(np.int8)
        out = {"w_q": jnp.asarray(wq), "w_scale": jnp.asarray(scale)}
        if "b" in p:
            out["b"] = p["b"]
        return out

    return _map_convs(pd, q_conv)


def decoder_is_quantized(pd) -> bool:
    hit = []

    def probe(p):
        if "w_q" in p:
            hit.append(True)
        return p

    _map_convs(pd, probe)
    return bool(hit)


def dequantize_decoder(pd):
    """Structural inverse, run *inside* the jitted decode program: int8
    weights rescale to float32 right before their conv (weight-only —
    activations never quantize).  A plain f32 tree passes through
    untouched, so every decode path calls this unconditionally."""
    if not decoder_is_quantized(pd):
        return pd

    def dq(p):
        if "w_q" not in p:
            return p
        out = {"w": p["w_q"].astype(jnp.float32) * p["w_scale"]}
        if "b" in p:
            out["b"] = p["b"]
        return out

    return _map_convs(pd, dq)
