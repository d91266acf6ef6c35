"""A unit voice with an ``sdar_moe`` backbone
(``sonata_tpu/models/unit_voice.py``) through ``speak_batch`` and the step
loop at a tiny size on the CPU: the loader, a row's launches as the engine
plans them, surplus units dropped, what the loop records of blocks and
passes, and what it still records of an ``lfm2_moe`` voice."""

import json
import threading
from pathlib import Path

import jax
import numpy as np
import pytest

from perfbench.harness import lfm2gen, sdargen
from sonata_tpu.core import OperationError
from sonata_tpu.models import from_config_path, voice_family
from sonata_tpu.models.config import SynthesisConfig
from sonata_tpu.models.sdar import SdarBackbone
from sonata_tpu.models.unit_backbone import RowPlan
from sonata_tpu.models.unit_voice import BACKBONES, UnitVoice
from sonata_tpu.serving import tracing
from sonata_tpu.synth import SpeechSynthesizer

ROOT = Path(__file__).resolve().parent.parent
DATA = ROOT / "tests/perfbench/data"
CONFIG = json.loads((DATA / "sdar-tiny.json").read_text())
LFM2 = json.loads((DATA / "lfm2-tiny.json").read_text())


def load(path, slots: int, positions: int):
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("SONATA_AR_SLOTS", str(slots))
        mp.setenv("SONATA_AR_POSITIONS", str(positions))
        return from_config_path(path)


@pytest.fixture(scope="module")
def voice_dir(tmp_path_factory):
    return sdargen.write_tensors(tmp_path_factory.mktemp("sdar_voice"),
                                 CONFIG)


@pytest.fixture(scope="module")
def voice(voice_dir):
    v = load(voice_dir, 3, 256)
    yield v
    v.close()


def wait_for(done, seconds=10.0):
    deadline = seconds / 0.05
    while not done() and deadline:
        threading.Event().wait(0.05)
        deadline -= 1
    return done()


def step_groups(tracer) -> list:
    return [s.attrs for t in tracer.recent_traces()
            if t.request_id.startswith("ar-steps-")
            for s in t.spans_snapshot() if s.name == "dispatch"]


def test_the_loader_picks_the_backbone_by_model_type(voice, voice_dir):
    assert isinstance(voice, UnitVoice)
    assert voice_family(voice_dir) == "unit_lm"
    assert sorted(BACKBONES) == ["gigachat3_5", "laguna", "lfm2_moe",
                                 "nemotron_h", "pangu_ultra_moe", "sdar_moe"]
    assert isinstance(voice.backbone, SdarBackbone)
    assert (voice.block_length, voice.denoising_steps) == (4, 2)
    assert voice.units.mask_id == 510
    # the head is a matrix of its own, read from the voice's directory
    assert voice.params["head"].shape == voice.params["embed"].shape
    assert not np.array_equal(np.asarray(voice.params["head"][:4], "float32"),
                              np.asarray(voice.params["embed"][:4], "float32"))
    layer = voice.params["layers"][2]
    drawn = sdargen.draw_layer(CONFIG, 2)
    assert layer["moe"]["w13"].dtype == jax.numpy.bfloat16
    assert np.array_equal(
        np.asarray(layer["moe"]["w13"][..., :32].astype("float32")),
        np.asarray(drawn["moe"]["w1"].astype("float32")))
    assert voice.expert_layers == [0, 1, 2]
    assert voice.lattice_shapes("minimal") == [("step",)]
    assert ("prefill", 32) in voice.lattice_shapes("full")
    # the published default of four denoising passes, where the voice
    # states none; and a voice that names another backbone is refused
    data = json.loads(Path(voice_dir).read_text())
    del data["units"]["denoising_steps"]
    assert SdarBackbone(data["backbone"], data["units"], 0).plan(
        8, 8).launches == 2 * 5
    data["backbone"]["model_type"] = "other_moe"
    bad = Path(voice_dir).parent / "bad.json"
    bad.write_text(json.dumps(data))
    with pytest.raises(Exception, match="not a unit voice"):
        from_config_path(bad)


@pytest.mark.parametrize("n,budget,launches,units", [
    (8, 8, 2 * 3, [0, 0, 0, 4, 4, 4, 8]),
    (11, 10, 4 * 3, [0, 0, 0, 1, 1, 1, 5, 5, 5, 9, 9, 9, 10]),
    (9, 3, 1 * 3, [0, 0, 0, 3]),
    (6, 1, 1 * 3, [0, 0, 0, 1])])
def test_a_rows_launches_are_counted_when_it_joins(voice, n, budget,
                                                   launches, units):
    """``ceil((n mod B + budget) / B) x (denoising_steps + 1)`` launches; a
    block's units are the row's after its commit pass, and the last
    block's surplus is not."""
    plan = voice.plan(n, budget)
    assert plan.launches == launches
    assert [plan.units(d) for d in range(launches + 1)] == units
    assert [plan.commits(d) for d in range(3)] == [False, False, True]
    assert [plan.attended(d) for d in (0, 2, 3)] == [
        n // 4 * 4 + 4] * 2 + [n // 4 * 4 + 8]
    assert voice.backbone.positions_needed(n, budget) == \
        n // 4 * 4 + launches // 3 * 4


def test_a_flagged_row_keeps_blocks_and_not_steps(voice):
    plan = RowPlan(launches=40 * 3, budget=158, block=4, passes=3)
    assert SdarBackbone.DUMP_EVERY == 16
    kept = [d for d in range(plan.launches) if voice.dumped(plan, d)]
    assert kept == [0, 1, 2, 48, 49, 50, 96, 97, 98, 117, 118, 119]
    backbone = BACKBONES["lfm2_moe"](lfm2gen.backbone(LFM2),
                                     LFM2["voice"]["units"], 0)
    lfm2 = backbone.plan(9, 70)
    # as before: the prefill's unit 0, then units 32, 64 and the last
    assert (lfm2.launches, lfm2.units(0), lfm2.units(69)) == (69, 1, 70)
    assert [d + 1 for d in range(69) if backbone.dumped(lfm2, d)] == [
        32, 64, 69]
    assert [lfm2.attended(d) for d in (0, 5)] == [10, 15]
    assert all(lfm2.commits(d) for d in range(69))


def test_greedy_synthesis_and_the_length_rule(voice):
    synth = SpeechSynthesizer(voice)
    voice.set_fallback_synthesis_config(SynthesisConfig(noise_scale=0.0))
    text = "hello there. a test."
    first = list(synth.synthesize_parallel(text))
    again = list(synth.synthesize_parallel(text))
    phonemes = list(synth.phonemize_text(text))
    assert len(first) == len(phonemes) == 2
    for audio, same, sentence in zip(first, again, phonemes):
        ids = voice.config.phonemes_to_ids(sentence)
        # exactly round(3.5 x ids) frames of 16 samples: the last block's
        # surplus units are dropped
        assert len(audio.samples) == 16 * round(3.5 * len(ids))
        assert np.array_equal(audio.samples.to_i16(), same.samples.to_i16())
    voice.set_fallback_synthesis_config(SynthesisConfig(noise_scale=2.0))
    warm = list(synth.synthesize_parallel(text))
    assert [len(a.samples) for a in warm] == [len(a.samples) for a in first]
    assert not np.array_equal(warm[0].samples.to_i16(),
                              first[0].samples.to_i16())
    voice.set_fallback_synthesis_config(SynthesisConfig(noise_scale=0.0))
    with pytest.raises(OperationError, match="does not fit a slot"):
        voice.speak_batch(["a" * 100])


def test_the_vocoder_reads_the_units_at_the_prompts_end(voice):
    """The units a row's samples come from are the token row's, from the
    prompt's end, ``budget`` of them: a second voice object over the same
    weights, driven by hand, gives the same samples."""
    voice.set_fallback_synthesis_config(SynthesisConfig(noise_scale=0.0))
    phonemes = list(voice.phonemize_text("a longer test."))[0]
    ids = voice.config.phonemes_to_ids(phonemes)
    budget = voice.frame_budget(len(ids))
    served = voice.speak_batch([phonemes])[0]
    cache = voice.new_cache()
    cache = voice.prefill(cache, 1, ids, 0.0)[0]
    live = np.array([False, True, False])
    plan = voice.plan(len(ids), budget)
    for k in range(plan.launches):
        cache, kept, _ = voice.step(cache, live, np.zeros((3,), np.float32),
                                    k)
    tokens = np.asarray(cache["tokens"][1])
    units = tokens[len(ids):len(ids) + budget]
    assert ((units >= 256) & (units < 510)).all()
    assert plan.units(plan.launches) == budget
    # the surplus of the last block was generated, and is not the row's
    end = voice.backbone.positions_needed(len(ids), budget)
    assert (tokens[len(ids) + budget:end] != 510).all()
    assert (tokens[end:] == 510).all()
    out, shape = voice.vocode(cache, 1, len(ids), budget)
    wav = voice.fetch_audio(out, budget)
    assert np.array_equal(np.asarray(served.samples.to_i16()),
                          np.asarray(type(served.samples)(wav).to_i16()))
    assert shape["frames_bucket"] >= budget


def test_a_flagged_rows_kept_logits_are_its_slots_rows_of_the_pass(voice):
    """A pass hands its logits out as the head wrote them, ``[S * B, V]``:
    what a flagged row keeps of it is cut where it is gathered
    (``take_rows``, 8 slots a call), its slot's ``B`` rows as ``[B, V]``,
    and ``dump``'s arrays have the shapes ``sdar_check.py`` reads."""
    from sonata_tpu.synth.steploop import DUMP_ROWS

    voice.set_fallback_synthesis_config(SynthesisConfig(noise_scale=0.0))
    ids = voice.config.phonemes_to_ids(
        list(voice.phonemize_text("a longer test."))[0])
    budget = voice.frame_budget(len(ids))
    cache = voice.new_cache()
    for slot in (0, 2):
        cache = voice.prefill(cache, slot, ids[slot:], 0.0)[0]
    live = np.array([True, False, True])
    b, vocab = voice.block_length, voice.params["head"].shape[0]
    kept = []
    for k in range(3):
        cache, gave, _ = voice.step(cache, live, np.zeros((3,), np.float32),
                                    k)
        x, logits, chose = gave
        assert x.shape == (3, b) and logits.shape == (3 * b, vocab)
        assert logits.dtype == np.float32 and chose.shape[:2] == (3, b)
        slots = [2, 0] + [0] * (DUMP_ROWS - 2)
        got = voice.take_rows(gave, slots)
        assert [a.shape[:2] for a in got] == [(DUMP_ROWS, b)] * 3
        for j, slot in enumerate(slots[:2]):
            assert np.array_equal(np.asarray(got[1][j]), np.asarray(
                logits[slot * b:slot * b + b]))
            assert np.array_equal(np.asarray(got[0][j]), np.asarray(x[slot]))
            assert np.array_equal(np.asarray(got[2][j]),
                                  np.asarray(chose[slot]))
        kept.append((k, [np.asarray(a)[0] for a in got]))
    dump = voice.dump(ids[2:], budget, kept, voice.row_record(cache, 2))
    t = voice.backbone.positions_needed(len(ids) - 2, budget)
    layers, top = chose.shape[2:]
    assert {k: v.shape for k, v in dump.items()} == {
        "tokens": (t,), "routes": (t, layers, top), "unmasked_at": (t,),
        "passes": (3,), "seen": (3, b), "logits": (3, b, vocab),
        "pass_routes": (3, b, layers, top), "block_length": (),
        "denoising_steps": (), "ids": (len(ids) - 2,)}
    assert dump["logits"].dtype == np.float32
    # the first block's commit pass saw what its two denoising passes left
    assert (dump["seen"][2] != voice.units.mask_id).all()


def test_the_loop_records_blocks_and_passes(voice):
    """Five callers over three slots: launches counted as the plans say,
    and the group spans and the counters carry the blocks' numbers."""
    voice.set_fallback_synthesis_config(SynthesisConfig(noise_scale=0.0))
    tracer = tracing.default_tracer()
    tracer.clear()
    stats = tracing.step_stats()
    before = (stats.units, dict(stats.row_passes), stats.slot_steps["live"],
              stats.rows["retired"])
    texts = ["one.", "two words.", "three short words.", "four.",
             "five more."]
    out, errors = {}, []

    def caller(k):
        try:
            with tracer.trace_request("test", request_id=f"row-{k}"):
                out[k] = voice.speak_batch(list(voice.phonemize_text(
                    texts[k])))[0]
        except Exception as e:
            errors.append(e)

    threads = [threading.Thread(target=caller, args=(k,), daemon=True)
               for k in range(5)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=120.0)
    assert not errors and len(out) == 5
    assert not any(t.is_alive() for t in threads)
    plans = []
    for k in range(5):
        ids = voice.config.phonemes_to_ids(
            list(voice.phonemize_text(texts[k]))[0])
        plans.append((len(ids), voice.plan(len(ids),
                                           voice.frame_budget(len(ids)))))
        assert len(out[k].samples) == 16 * plans[-1][1].budget
    launches = sum(p.launches for _, p in plans)
    assert wait_for(lambda: stats.slot_steps["live"] - before[2] == launches)
    assert stats.rows["retired"] - before[3] == 5
    assert stats.units - before[0] == sum(p.budget for _, p in plans)
    assert stats.row_passes["commit"] - before[1]["commit"] == launches // 3
    assert stats.row_passes["denoise"] - before[1]["denoise"] \
        == 2 * launches // 3
    traces = {t.request_id: t for t in tracer.recent_traces()}
    for k, (n, plan) in enumerate(plans):
        spans = {s.attrs.get("kind"): s.attrs for s in traces[f"row-{k}"]
                 .spans_snapshot() if s.name == "dispatch"}
        assert sorted(spans) == ["prefill", "vocode"]
        assert (spans["prefill"]["tokens"], spans["prefill"]["blocks"],
                spans["prefill"]["tail_ids"]) == (n, n // 4, n % 4)
        assert spans["vocode"]["frames_needed"] == plan.budget
    groups = step_groups(tracer)
    assert groups and all(
        g["kind"] == "step" and g["slots"] == 3 and g["block_length"] == 4
        and g["denoising_steps"] == 2 and g["layers"] == [0, 1, 2]
        for g in groups)
    for g in groups:
        assert g["positions"] == 4 * g["live_slot_steps"]
        assert g["denoise_row_passes"] + g["commit_row_passes"] \
            == g["live_slot_steps"] <= 3 * g["steps"]
        # a commit pass leaves a row with a block of units, less what the
        # prompt's tail took of the first and the budget cut off the last
        assert g["units"] <= 4 * g["commit_row_passes"]
        assert sum(g["assignments"]) == 3 * 2 * g["positions"]
        assert g["kv_positions"] >= 8 * g["live_slot_steps"]
    assert sum(g["live_slot_steps"] for g in groups) == launches
    assert sum(g["units"] for g in groups) == sum(p.budget for _, p in plans)
    assert sum(g["commit_row_passes"] for g in groups) == launches // 3


def test_an_lfm2_voice_still_reads_a_unit_a_live_slot_step(tmp_path):
    path = lfm2gen.write_tensors(tmp_path, LFM2)
    v = load(path, 2, 256)
    tracer = tracing.default_tracer()
    tracer.clear()
    stats = tracing.step_stats()
    before = (stats.units, dict(stats.row_passes))
    try:
        assert (v.block_length, v.denoising_steps) == (1, 0)
        v.set_fallback_synthesis_config(SynthesisConfig(noise_scale=0.0))
        with tracer.trace_request("test", request_id="lfm2-rows"):
            audios = v.speak_batch(list(v.phonemize_text(
                "one. two words.")))
        units = sum(len(a.samples) // 16 for a in audios)
        assert wait_for(lambda: stats.units - before[0] == units)
    finally:
        v.close()
    groups = step_groups(tracer)
    assert groups and all(
        g["units"] == g["live_slot_steps"] - g["admit_steps"]
        == g["positions"]
        == g["commit_row_passes"] and g["denoise_row_passes"] == 0
        and g["block_length"] == 1 and g["denoising_steps"] == 0
        for g in groups)
    # the launch that carried a row's prompt gave it its first unit; the
    # launches it stepped in gave the rest
    assert sum(g["admit_steps"] for g in groups) == 2
    assert sum(g["units"] for g in groups) == units - 2
    assert stats.row_passes["denoise"] == before[1]["denoise"]
    spans = [s.attrs for t in tracer.recent_traces()
             for s in t.spans_snapshot()
             if s.name == "dispatch" and s.attrs.get("kind") == "prefill"]
    assert spans and all(s["blocks"] == s["tokens"] and s["tail_ids"] == 0
                         for s in spans)
