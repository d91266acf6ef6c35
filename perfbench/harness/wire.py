"""The few messages of ``sonata_grpc.proto`` the benchmark sends and reads,
encoded by hand (proto3 wire format), so that the yardstick does not import
the program's message classes."""

from __future__ import annotations

import struct

MODES = {"LAZY": 1, "PARALLEL": 2, "BATCHED": 3}
SERVICE = "/sonata_grpc.sonata_grpc/"


def _varint(n: int) -> bytes:
    out = bytearray()
    while True:
        b = n & 0x7F
        n >>= 7
        out.append(b | (0x80 if n else 0))
        if not n:
            return bytes(out)


def _field(number: int, payload: bytes) -> bytes:
    return _varint(number << 3 | 2) + _varint(len(payload)) + payload


def _uint(number: int, value: int) -> bytes:
    return _varint(number << 3) + _varint(value) if value else b""


def _read_varint(data: bytes, i: int) -> tuple:
    value = shift = 0
    while True:
        b = data[i]
        i += 1
        value |= (b & 0x7F) << shift
        shift += 7
        if not b & 0x80:
            return value, i


def decode(data: bytes) -> dict:
    """``{field number: [values]}``; length-delimited fields stay bytes."""
    out: dict = {}
    i = 0
    while i < len(data):
        key, i = _read_varint(data, i)
        number, kind = key >> 3, key & 7
        if kind == 0:
            value, i = _read_varint(data, i)
        elif kind == 2:
            size, i = _read_varint(data, i)
            value = data[i:i + size]
            i += size
        elif kind == 5:
            value = struct.unpack("<f", data[i:i + 4])[0]
            i += 4
        elif kind == 1:
            value = struct.unpack("<d", data[i:i + 8])[0]
            i += 8
        else:
            raise ValueError(f"wire type {kind}")
        out.setdefault(number, []).append(value)
    return out


def voice_path(config_path: str) -> bytes:
    return _field(1, config_path.encode())


def voice_info(data: bytes) -> dict:
    msg = decode(data)
    audio = decode(msg.get(4, [b""])[0])
    return {"voice_id": msg[1][0].decode(),
            "sample_rate": audio.get(1, [0])[0]}


def utterance(voice_id: str, text: str, mode: str = "",
              chunk_size: int = 0, chunk_padding: int = 0) -> bytes:
    return (_field(1, voice_id.encode()) + _field(2, text.encode())
            + _uint(4, MODES.get(mode, 0)) + _uint(5, chunk_size)
            + _uint(6, chunk_padding))


def _float(number: int, value: float) -> bytes:
    """Always on the wire, zero too: the server reads presence."""
    return _varint(number << 3 | 5) + struct.pack("<f", value)


def synthesis_options(voice_id: str, speaker: str = None,
                      noise_scale: float = None,
                      noise_w: float = None) -> bytes:
    """A VoiceSynthesisOptions message; fields left ``None`` stay as the
    voice has them."""
    options = b""
    if speaker is not None:
        options += _field(1, speaker.encode())
    if noise_scale is not None:
        options += _float(3, noise_scale)
    if noise_w is not None:
        options += _float(4, noise_w)
    return _field(1, voice_id.encode()) + _field(2, options)


def wav_samples(data: bytes) -> bytes:
    """``wav_samples`` of a SynthesisResult or a WaveSamples message."""
    return decode(data).get(1, [b""])[0]
