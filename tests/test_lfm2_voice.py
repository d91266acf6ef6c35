"""The unit voice (``sonata_tpu/models/unit_voice.py``) and its step loop
(``sonata_tpu/synth/steploop.py``) at a tiny size on the CPU: the loader of
a voice directory, the family key, the length rule, greedy synthesis, rows
joining a loop that is already running, and what the loop records."""

import json
import threading
from pathlib import Path

import jax
import numpy as np
import pytest

from perfbench.harness import lfm2gen, voicegen
from sonata_tpu.core import Model, OperationError
from sonata_tpu.models import PiperVoice, from_config_path, voice_family
from sonata_tpu.models.config import SynthesisConfig
from sonata_tpu.models.unit_voice import UnitVoice, place_weights
from sonata_tpu.serving import tracing
from sonata_tpu.synth import SpeechSynthesizer
from tests.voices import write_tiny_voice

ROOT = Path(__file__).resolve().parent.parent
DATA = ROOT / "tests/perfbench/data"
CONFIG = json.loads((DATA / "lfm2-tiny.json").read_text())


def load(path, slots: int, positions: int):
    """The voice at ``path`` with the operator's two sizes set."""
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("SONATA_AR_SLOTS", str(slots))
        mp.setenv("SONATA_AR_POSITIONS", str(positions))
        return from_config_path(path)


@pytest.fixture(scope="module")
def voice_dir(tmp_path_factory):
    out = tmp_path_factory.mktemp("lfm2_voice")
    return lfm2gen.write_tensors(out, CONFIG)


@pytest.fixture(scope="module")
def voice(voice_dir):
    v = load(voice_dir, 3, 256)
    yield v
    v.close()


def test_the_loader_reads_a_voice_directory_of_real_tensors(voice, voice_dir):
    assert isinstance(voice, UnitVoice) and isinstance(voice, Model)
    assert voice_family(voice_dir) == "unit_lm"
    assert voice.properties()["family"] == "unit_lm"
    layer = voice.params["layers"][3]
    drawn = lfm2gen.draw_layer(CONFIG, 3)
    assert layer["ffn"]["w13"].dtype == jax.numpy.bfloat16
    assert np.array_equal(
        np.asarray(layer["ffn"]["w13"][..., :32].astype("float32")),
        np.asarray(drawn["ffn"]["w1"].astype("float32")))
    assert np.array_equal(np.asarray(layer["ffn"]["expert_bias"]),
                          np.asarray(drawn["ffn"]["expert_bias"]))
    assert np.array_equal(np.asarray(voice.unit_table),
                          np.asarray(lfm2gen.draw(CONFIG, "unit_table")))
    want = voicegen.unflatten(lfm2gen.generator_flat(CONFIG))
    assert np.array_equal(np.asarray(voice.generator["dec"]["conv_pre"]["w"]),
                          want["dec"]["conv_pre"]["w"])
    assert voice.audio_output_info().sample_rate == 16000
    assert voice.lattice_shapes("minimal") == [("step",)]
    # a row's prompt rides a step: no prefill program is in the lattice
    full = voice.lattice_shapes("full")
    assert ("step_admit", 32) in full
    assert not any(shape[0] == "prefill" for shape in full)


def test_placed_weights_are_taken_once_and_a_bare_directory_fails(
        voice, tmp_path):
    path = lfm2gen.write_voice(tmp_path, CONFIG)
    with pytest.raises(Exception, match="no tensor"):
        from_config_path(path)
    place_weights(path, {"backbone": voice.params,
                         "unit_table": voice.unit_table,
                         "generator": voice.generator})
    placed = load(path, 2, 64)
    try:
        assert placed.params is voice.params
        with pytest.raises(Exception, match="no tensor"):
            from_config_path(path)
    finally:
        placed.close()
    with pytest.raises(OperationError, match="mesh"):
        from_config_path(path, mesh=object())


@pytest.mark.parametrize("name", ["tiny", "tiny-multi", "seam", "helper"])
def test_every_piper_json_still_loads_a_piper_voice(name, tmp_path):
    if name == "helper":
        path = write_tiny_voice(tmp_path)
    else:
        config = json.loads((DATA / f"{name}.json").read_text())
        path = voicegen.write_voice(tmp_path, config)
    assert voice_family(path) == "piper"
    v = from_config_path(path)
    try:
        assert type(v) is PiperVoice
    finally:
        v.close()


def test_the_length_rule_and_greedy_synthesis(voice):
    synth = SpeechSynthesizer(voice)
    greedy = SynthesisConfig(noise_scale=0.0)
    voice.set_fallback_synthesis_config(greedy)
    text = "hello there. a test."
    first = list(synth.synthesize_parallel(text))
    again = list(synth.synthesize_parallel(text))
    phonemes = list(synth.phonemize_text(text))
    assert len(first) == len(phonemes) == 2
    for audio, same, sentence in zip(first, again, phonemes):
        ids = voice.config.phonemes_to_ids(sentence)
        # exactly round(3.5 x ids) frames of 16 samples
        assert len(audio.samples) == 16 * round(3.5 * len(ids))
        assert np.array_equal(audio.samples.to_i16(), same.samples.to_i16())
    voice.set_fallback_synthesis_config(SynthesisConfig(noise_scale=2.0))
    warm = list(synth.synthesize_parallel(text))
    assert [len(a.samples) for a in warm] == [len(a.samples) for a in first]
    assert not np.array_equal(warm[0].samples.to_i16(),
                              first[0].samples.to_i16())
    # twice the length scale: twice the frames
    voice.set_fallback_synthesis_config(SynthesisConfig(
        noise_scale=0.0, length_scale=2.0))
    long = list(synth.synthesize_parallel("a test."))
    assert len(long[0].samples) == 16 * round(7.0 * len(
        voice.config.phonemes_to_ids(phonemes[1])))
    voice.set_fallback_synthesis_config(greedy)
    with pytest.raises(OperationError, match="does not fit a slot"):
        voice.speak_batch(["a" * 100])
    with pytest.raises(OperationError, match="one speaker"):
        voice.speak_batch(["a"], speakers=[3])


def test_rows_join_a_running_loop_and_the_loop_records_what_it_ran(voice):
    """Six callers over three slots: rows wait, join as slots free, and
    every request's trace holds its own prefill and vocode spans, while the
    steps go to the loop's own trace, counted once."""
    voice.set_fallback_synthesis_config(SynthesisConfig(noise_scale=0.0))
    tracer = tracing.default_tracer()
    tracer.clear()
    stats = tracing.step_stats()
    before = (stats.steps, dict(stats.rows), stats.slot_steps["live"])
    # the counters are the process's and another voice's layers may be there
    # already: what this voice's rows move must be its expert layers alone
    moe_before = {layer: list(sums) for layer, sums in stats.moe.items()}
    texts = ["one.", "two words.", "three short words.", "four.",
             "five more.", "six is the last."]
    alone = [voice.speak_batch(list(voice.phonemize_text(t)))[0]
             for t in texts[:2]]
    out, errors = {}, []

    def caller(k):
        try:
            with tracer.trace_request("test", request_id=f"row-{k}"):
                out[k] = voice.speak_batch(list(voice.phonemize_text(
                    texts[k])))[0]
        except Exception as e:
            errors.append(e)

    threads = [threading.Thread(target=caller, args=(k,), daemon=True)
               for k in range(6)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=120.0)
    assert not errors and len(out) == 6
    assert not any(t.is_alive() for t in threads)
    # a row's audio does not depend on who shared its steps
    for k in range(2):
        assert np.array_equal(out[k].samples.to_i16(),
                              alone[k].samples.to_i16())
    assert stats.rows["admitted"] - before[1]["admitted"] == 8
    assert stats.rows["retired"] - before[1]["retired"] == 8
    units = sum(len(a.samples) // 16 for a in list(out.values()) + alone)
    # a row of N units holds a slot for N launches: the one that carries
    # its prompt (its first unit is that launch's) and N - 1 steps
    deadline = 50
    while stats.slot_steps["live"] - before[2] < units and deadline:
        threading.Event().wait(0.1)
        deadline -= 1
    assert stats.slot_steps["live"] - before[2] == units
    assert stats.slots_in_use == 0
    assert {layer for layer, sums in stats.moe.items()
            if list(sums) != moe_before.get(layer)} == {2, 3, 4, 5}
    traces = {t.request_id: t for t in tracer.recent_traces()}
    for k in range(6):
        spans = {s.attrs.get("kind"): s for s in traces[f"row-{k}"]
                 .spans_snapshot() if s.name == "dispatch"}
        assert sorted(spans) == ["prefill", "vocode"]
        # both end when what their program gave is on the host; the
        # vocoder's says what the row needed, what it was padded to and
        # what the finisher spent on it
        assert spans["prefill"].end <= spans["vocode"].end
        v = spans["vocode"].attrs
        assert v["frames_needed"] == len(out[k].samples) // 16 \
            <= v["frames_bucket"]
        assert v["fetch_wait_ms"] >= 0.0 and v["finish_ms"] > 0.0
    groups = [s.attrs for rid, t in traces.items()
              if rid.startswith("ar-steps-") for s in t.spans_snapshot()
              if s.name == "dispatch"]
    assert groups and all(g["kind"] == "step" and g["slots"] == 3
                          and g["layers"] == [2, 3, 4, 5] for g in groups)
    assert sum(g["steps"] for g in groups) <= stats.steps - before[0]
    assert all(0 < g["live_slot_steps"] <= 3 * g["steps"] for g in groups)
    # a launch's load is its live rows' and the prompt's it carried
    assert all(sum(g["assignments"]) == 4 * 2 * (
        g["live_slot_steps"] - g["admit_steps"] + g["prompt_tokens"])
        for g in groups)
    assert sum(g["admit_steps"] for g in groups) == sum(
        g["arrivals"] for g in groups) > 0


def test_a_closed_voice_refuses_and_fails_what_waits(voice_dir):
    v = load(voice_dir, 1, 256)
    v.speak_batch(["a."])
    v.close()
    with pytest.raises(OperationError, match="closed"):
        v.speak_batch(["a."])
