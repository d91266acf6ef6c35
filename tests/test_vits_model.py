"""VITS model-layer tests: config parsing, phoneme-id encoding, staged
inference, batching, streaming, serialization.

Mirrors what the reference *cannot* test hermetically (SURVEY §4 tier 3) —
our tiny random voices make the full pipeline testable without downloads,
with golden-metric assertions (durations, shapes, finiteness) instead of
"doesn't crash".
"""

import json

import numpy as np
import pytest

from sonata_tpu.models import ModelConfig, SynthesisConfig
from sonata_tpu.models.chunker import MIN_CHUNK_SIZE, plan_chunks
from sonata_tpu.models.serialization import (
    flatten_params,
    load_params,
    save_params,
)

from voices import tiny_multispeaker_voice, tiny_voice


@pytest.fixture(scope="module")
def voice():
    return tiny_voice()


# ---------------------------------------------------------------------------
# config + encoding (piper/src/lib.rs:144-158, 232-250)
# ---------------------------------------------------------------------------

def test_model_config_from_json(tmp_path):
    cfg = {
        "audio": {"sample_rate": 22050, "quality": "medium"},
        "num_speakers": 2,
        "speaker_id_map": {"alice": 0, "bob": 1},
        "espeak": {"voice": "en-us"},
        "inference": {"noise_scale": 0.5, "length_scale": 1.2, "noise_w": 0.7},
        "num_symbols": 10,
        "phoneme_id_map": {"_": [0], "^": [1], "$": [2], "a": [3], "b": [4]},
    }
    p = tmp_path / "voice.onnx.json"
    p.write_text(json.dumps(cfg))
    mc = ModelConfig.from_path(p)
    assert mc.sample_rate == 22050
    assert mc.num_speakers == 2
    assert mc.inference.length_scale == pytest.approx(1.2)
    assert mc.reversed_speaker_map() == {0: "alice", 1: "bob"}


def test_phonemes_to_ids_interleaved_pad():
    mc = ModelConfig.from_dict({
        "phoneme_id_map": {"_": [0], "^": [1], "$": [2], "a": [3], "b": [4]},
        "num_symbols": 5,
    })
    # [bos] a pad b pad [eos]; unknown 'z' silently dropped
    assert mc.phonemes_to_ids("azb") == [1, 3, 0, 4, 0, 2]


def test_phonemes_to_ids_multi_id_chars():
    # reference parity (piper/src/lib.rs phonemes_to_input_ids): a
    # multi-id map entry contributes only its FIRST id, then the
    # interleaved pad — never the whole list
    mc = ModelConfig.from_dict({
        "phoneme_id_map": {"_": [0], "^": [1], "$": [2], "ʧ": [5, 6]},
        "num_symbols": 7,
    })
    assert mc.phonemes_to_ids("ʧ") == [1, 5, 0, 2]
    # the diag variant agrees and reports no drops for a mapped symbol
    ids, dropped = mc.phonemes_to_ids_diag("ʧʧ")
    assert ids == [1, 5, 0, 5, 0, 2]
    assert dropped == []


def test_phonemes_to_ids_empty_map_entry_drops_not_crashes():
    # a present-but-empty entry in a user-supplied config must degrade
    # like an unknown symbol, not IndexError the encode path
    mc = ModelConfig.from_dict({
        "phoneme_id_map": {"_": [0], "^": [1], "$": [2], "a": [3],
                           "x": []},
        "num_symbols": 5,
    })
    ids, dropped = mc.phonemes_to_ids_diag("axa")
    assert ids == [1, 3, 0, 3, 0, 2]
    assert dropped == ["x"]


def test_synthesis_config_roundtrip(voice):
    sc = voice.get_fallback_synthesis_config()
    sc.length_scale = 2.0
    voice.set_fallback_synthesis_config(sc)
    assert voice.get_fallback_synthesis_config().length_scale == 2.0
    voice.set_fallback_synthesis_config(voice.get_default_synthesis_config())
    with pytest.raises(Exception):
        voice.set_fallback_synthesis_config({"not": "a config"})


# ---------------------------------------------------------------------------
# end-to-end synthesis
# ---------------------------------------------------------------------------

def test_speak_one_sentence(voice):
    audio = voice.speak_one_sentence("həloʊ wɜːld.")
    assert audio.sample_rate == 16000
    s = audio.samples.data
    assert len(s) > 0 and len(s) % voice.hp.hop_length == 0
    assert np.isfinite(s).all()
    assert audio.inference_ms > 0
    assert audio.real_time_factor() > 0


def test_speak_batch_true_batching(voice):
    batch = ["həloʊ.", "ɡʊd wɜːld ɪz hɪɹ tuːdeɪ.", "aɪ."]
    audios = voice.speak_batch(batch)
    assert len(audios) == 3
    lengths = [len(a.samples) for a in audios]
    assert all(n > 0 for n in lengths)
    # longer phoneme strings should synthesize more audio
    assert lengths[1] > lengths[2]


def test_phonemize_then_speak(voice):
    ph = voice.phonemize_text("Hello world. How are you?")
    assert len(ph) == 2
    audios = voice.speak_batch(list(ph))
    assert len(audios) == 2


def test_multispeaker_conditioning():
    v = tiny_multispeaker_voice()
    assert v.get_speakers() == {0: "spk0", 1: "spk1", 2: "spk2", 3: "spk3"}
    sc = v.get_fallback_synthesis_config()
    sc.speaker = ("spk2", 2)
    v.set_fallback_synthesis_config(sc)
    audio = v.speak_one_sentence("tɛst.")
    assert len(audio.samples) > 0
    assert v.speaker_name_to_id("spk1") == 1
    assert v.speaker_id_to_name(3) == "spk3"


# ---------------------------------------------------------------------------
# streaming (chunker + stream_synthesis)
# ---------------------------------------------------------------------------

def test_chunk_plans_partition_exactly():
    total, chunk, pad = 500, 45, 3
    plans = plan_chunks(total, chunk, pad)
    assert len(plans) > 1
    emitted = sum(p.width - p.trim_left - p.trim_right for p in plans)
    assert emitted == total
    # consecutive windows overlap by 2*padding
    for a, b in zip(plans, plans[1:]):
        assert a.win_end - b.win_start == 2 * pad
    # no tail shorter than MIN_CHUNK_SIZE
    last_body = plans[-1].width - plans[-1].trim_left - plans[-1].trim_right
    assert last_body >= MIN_CHUNK_SIZE


def test_chunk_plans_one_shot():
    plans = plan_chunks(80, 45, 3)  # 80 <= 2*45+6
    assert plans == [plans[0]]
    assert plans[0].win_start == 0 and plans[0].win_end == 80


def test_stream_synthesis_chunks(voice):
    ph = "ðɪs ɪz ə lɑːŋ tɛst sɛntəns wɪð mɛni wɜːdz ænd saʊndz tuː stɹiːm."
    chunks = list(voice.stream_synthesis(ph, chunk_size=20, chunk_padding=2))
    assert len(chunks) >= 1
    total = sum(len(c.samples) for c in chunks)
    assert total > 0 and total % voice.hp.hop_length == 0
    for c in chunks:
        assert np.isfinite(c.samples.data).all()
        assert c.inference_ms > 0


def test_streaming_matches_batch_total_frames(voice):
    # same phonemes: the stream's total sample count equals total_frames*hop
    # for its own draw (cannot compare waveforms across RNG draws)
    ph = "wʌn tuː θɹiː fɔːɹ faɪv sɪks sɛvən eɪt naɪn tɛn ilɛvən twɛlv."
    chunks = list(voice.stream_synthesis(ph, chunk_size=15, chunk_padding=2))
    total_stream = sum(len(c.samples) for c in chunks)
    assert total_stream % voice.hp.hop_length == 0
    assert len(chunks) > 1


# ---------------------------------------------------------------------------
# serialization
# ---------------------------------------------------------------------------

def test_params_save_load_roundtrip(tmp_path, voice):
    path = tmp_path / "params.npz"
    save_params(path, voice.params)
    back = load_params(path)
    flat_a = flatten_params(voice.params)
    flat_b = flatten_params(back)
    assert flat_a.keys() == flat_b.keys()
    for k in flat_a:
        np.testing.assert_array_equal(flat_a[k], flat_b[k])


def test_voice_from_config_path_with_npz(tmp_path, voice):
    cfg = {
        "audio": {"sample_rate": 16000, "quality": None},
        "num_symbols": voice.config.num_symbols,
        "phoneme_id_map": voice.config.phoneme_id_map,
        "espeak": {"voice": "en-us"},
        "model": dict(
            inter_channels=32, hidden_channels=32, filter_channels=64,
            n_heads=2, n_layers=2, upsample_rates=[4, 4],
            upsample_initial_channel=64, upsample_kernel_sizes=[8, 8],
            resblock_kernel_sizes=[3], resblock_dilation_sizes=[[1, 3]],
            dp_filter_channels=32, gin_channels=16, flow_n_layers=2,
            flow_wn_layers=2,
        ),
    }
    (tmp_path / "v.onnx.json").write_text(json.dumps(cfg))
    save_params(tmp_path / "v.npz", voice.params)
    from sonata_tpu.models import from_config_path

    v2 = from_config_path(tmp_path / "v.onnx.json")
    audio = v2.speak_one_sentence("tɛst.")
    assert len(audio.samples) > 0


def test_out_of_range_speaker_id_raises():
    from sonata_tpu.core import OperationError

    v = tiny_multispeaker_voice()
    sc = v.get_fallback_synthesis_config()
    sc.speaker = ("ghost", 99)
    v.set_fallback_synthesis_config(sc)
    with pytest.raises(OperationError):
        v.speak_one_sentence("tɛst.")


def test_batch_is_bucketed(voice):
    # 3 sentences must pad to the 4-batch bucket: one compiled executable
    # shared by any 3-or-4 sentence batch
    audios = voice.speak_batch(["tɛst.", "wʌn.", "tuː."])
    assert len(audios) == 3
    key_batches = {k[0] for k in voice._full_cache}
    assert 3 not in key_batches and 4 in key_batches


def test_batch_preserves_relative_loudness(voice):
    # device-side i16 quantization must not flatten per-sentence amplitude
    audios = voice.speak_batch(["ə.", "loʊd ʃaʊt wɜːdz hɪɹ naʊ."])
    peaks = [float(np.max(np.abs(a.samples.data))) for a in audios]
    assert all(p > 0 for p in peaks)
    assert abs(peaks[0] - peaks[1]) > 1e-5  # not both pinned to one scale


def test_overflow_retry_reproduces_exact_durations():
    # force the estimator to undershoot so the retry path runs, and check
    # the result matches a fresh voice without the undershoot (same seed →
    # same RNG sequence → identical audio)
    va = tiny_voice(seed=21)
    vb = tiny_voice(seed=21)
    # guarantees overflow on first dispatch
    vb.frame_estimator.frames_per_id = 0.01
    a = va.speak_one_sentence("ə lɑːŋɚ tɛst sɛntəns wɪð mɔːɹ wɜːdz.")
    b = vb.speak_one_sentence("ə lɑːŋɚ tɛst sɛntəns wɪð mɔːɹ wɜːdz.")
    assert len(a.samples) == len(b.samples)
    np.testing.assert_allclose(a.samples.data, b.samples.data, atol=1e-4)


def test_speak_batch_partitions_by_text_bucket(voice):
    # short + long sentences: groups dispatch separately but results come
    # back in input order with correct relative durations
    short = "aɪ."
    long = ("ðɪs ɪz ə mʌtʃ lɑːŋɚ sɛntəns wɪð mɛni mɔːɹ wɜːdz ænd saʊndz "
            "tuː meɪk ɪt pæs ðə fɜːst tɛkst bʌkɪt baʊndɚɹi ʃʊɹli.")
    audios = voice.speak_batch([long, short, long, short])
    assert len(audios) == 4
    assert len(audios[0].samples) > len(audios[1].samples)
    assert len(audios[2].samples) > len(audios[3].samples)
    assert len(audios[1].samples) > 0


def test_per_row_speakers_in_one_batch():
    v = tiny_multispeaker_voice()
    # deterministic synthesis (no noise): any waveform difference can only
    # come from the speaker conditioning, so dropped sid plumbing would
    # make this fail
    sc = v.get_fallback_synthesis_config()
    sc.noise_scale = 0.0
    sc.noise_w = 0.0
    v.set_fallback_synthesis_config(sc)
    ph = "seɪm wɜːdz hɪɹ."
    audios = v.speak_batch([ph, ph, ph], speakers=[0, 3, None])
    assert len(audios) == 3
    # None falls back to the config speaker (0) → identical to row 0
    np.testing.assert_array_equal(audios[0].samples.data,
                                  audios[2].samples.data)
    # different speaker embeddings → different waveforms for identical text
    assert not np.array_equal(audios[0].samples.data, audios[1].samples.data)
    with pytest.raises(Exception):
        v.speak_batch([ph], speakers=[99])
    with pytest.raises(Exception):
        v.speak_batch([ph, ph], speakers=[0])  # length mismatch


def test_single_speaker_voice_rejects_other_speakers(voice):
    from sonata_tpu.core import OperationError

    with pytest.raises(OperationError):
        voice.speak_batch(["tɛst."], speakers=[2])
    # speaker 0 / None are fine on a single-speaker voice
    ok = voice.speak_batch(["tɛst.", "tɛst."], speakers=[0, None])
    assert len(ok) == 2


def test_quality_preset_x_low():
    # x_low preset: slim dims (96 channels, 256 decoder base)
    from sonata_tpu.models.config import ModelConfig

    mc = ModelConfig.from_dict({
        "audio": {"sample_rate": 16000, "quality": "x_low"},
        "num_symbols": 5,
        "phoneme_id_map": {"_": [0], "^": [1], "$": [2], "a": [3]},
    })
    assert mc.hyper.hidden_channels == 96
    assert mc.hyper.upsample_initial_channel == 256
    assert mc.hyper.hop_length == 256


def test_per_row_scales_in_one_batch(voice):
    # per-request length_scale inside one dispatch: row 1 at 3x must be
    # about 3x longer than row 0 at 1x for identical text
    long_cfg = SynthesisConfig(length_scale=3.0, noise_scale=0.0, noise_w=0.0)
    base_cfg = SynthesisConfig(length_scale=1.0, noise_scale=0.0, noise_w=0.0)
    ph = "seɪm wɜːdz hɪɹ tʊdeɪ."
    audios = voice.speak_batch([ph, ph], scales=[base_cfg, long_cfg])
    n0, n1 = len(audios[0].samples), len(audios[1].samples)
    assert n1 > 2.3 * n0
    with pytest.raises(Exception):
        voice.speak_batch([ph], scales=[base_cfg, long_cfg])  # len mismatch


# ---------------------------------------------------------------------------
# reduced-precision compute policy (SONATA_COMPUTE_DTYPE / compute_dtype)
# ---------------------------------------------------------------------------

def test_compute_dtype_parsing(monkeypatch):
    import jax.numpy as jnp

    from sonata_tpu.core import OperationError

    from voices import tiny_voice

    assert tiny_voice().compute_dtype is None
    assert tiny_voice(seed=1).compute_dtype is None
    v = tiny_voice(seed=2)
    assert v.compute_dtype is None
    for spelling in ("bfloat16", "bf16"):
        assert PiperVoiceCD(spelling).compute_dtype == jnp.bfloat16
    for spelling in ("float32", "f32", None):
        assert PiperVoiceCD(spelling).compute_dtype is None
    with pytest.raises(OperationError):
        PiperVoiceCD("float16")
    # env var drives the default
    monkeypatch.setenv("SONATA_COMPUTE_DTYPE", "bfloat16")
    assert tiny_voice(seed=3).compute_dtype == jnp.bfloat16


def PiperVoiceCD(spelling):
    from voices import tiny_voice

    return tiny_voice(seed=9, compute_dtype=spelling)


def test_bfloat16_decode_close_to_float32():
    # same voice, same seed, bf16 conv stack: audio must stay close to the
    # float32 waveform (output itself returns to f32 before tanh)
    from voices import tiny_voice

    ph = "ðɪs ɪz ə tɛst sɛntəns."
    a32 = tiny_voice(seed=4).speak_batch([ph])[0]
    a16 = tiny_voice(seed=4, compute_dtype="bfloat16").speak_batch([ph])[0]
    assert len(a32.samples) == len(a16.samples)
    x32 = np.asarray(a32.samples.data, np.float64)
    x16 = np.asarray(a16.samples.data, np.float64)
    assert np.isfinite(x16).all()
    err = x16 - x32
    denom = max(float((x32 ** 2).mean()), 1e-12)
    snr_db = 10 * np.log10(denom / max(float((err ** 2).mean()), 1e-30))
    assert snr_db > 25.0, f"bf16 decode SNR too low: {snr_db:.1f} dB"


def test_bfloat16_streaming_window_decode():
    # the streaming window decoder caches carry the policy too
    from voices import tiny_voice

    v = tiny_voice(seed=5, compute_dtype="bf16")
    chunks = list(v.stream_synthesis("ə lɒŋɡɚ tɛst sɛntəns hɪɹ.", 12, 2))
    assert chunks and all(np.isfinite(np.asarray(c.samples.data)).all()
                          for c in chunks)


def test_prewarm_compiles_common_shapes():
    from voices import tiny_voice

    v = tiny_voice(seed=8)
    assert not v._full_cache
    n = v.prewarm(texts=["Short one.", "A slightly longer warm sentence."],
                  streaming=True, chunk_size=12, chunk_padding=2)
    assert n == len(v._full_cache) and n > 0
    # streaming prewarm compiled the staged path too
    assert v._enc_cache and v._dec_cache
