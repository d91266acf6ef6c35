"""Units of the benchmark's harness: traffic generation, metric arithmetic,
the wire codec, the cost functions, the trace reduction and the shape of
``BENCHMARK.json``.  No server, no jax."""

import collections
import json
import math
import random
import re
from pathlib import Path

import pytest

from perfbench.harness import (costs, parts, server, shapes, stats, textgen,
                               trace, voicegen, wire)

ROOT = Path(__file__).resolve().parents[2]
DATA = Path(__file__).resolve().parent / "data"
BENCHMARK = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")


@pytest.fixture(scope="module")
def lexicon():
    return textgen.Lexicon(ROOT / "perfbench" / "traffic" / "words.tsv")


def traffic_files():
    return sorted((ROOT / "perfbench" / "traffic").glob("*.json"))


@pytest.mark.parametrize("file", traffic_files(), ids=lambda p: p.stem)
@pytest.mark.parametrize("seeds", [(1, 2), (7, 3000000019)])
def test_same_multiset_of_lengths_for_any_two_seeds(file, seeds, lexicon):
    traffic = json.loads(file.read_text())
    id_map = json.loads((ROOT / "perfbench/configs/lessac-high.json")
                        .read_text())["voice"]["phoneme_id_map"]
    n = len(traffic["paragraphs"])
    shapes = []
    for seed in seeds:
        order = textgen.schedule(traffic, seed)
        assert sorted(order[:n]) == list(range(n))      # one pass, permuted
        rows = collections.Counter()
        for seq, para in enumerate(order[:n]):
            rng = random.Random(seed * 1000003 + seq)
            for s in textgen.paragraph_text(
                    lexicon, traffic["paragraphs"][para], rng):
                rows[len(textgen.text_to_ids(lexicon, s, id_map))] += 1
        shapes.append(rows)
    assert shapes[0] == shapes[1]
    assert textgen.schedule(traffic, seeds[0]) != textgen.schedule(
        traffic, seeds[1])


@pytest.mark.parametrize("n", [12, 34, 57, 75, 96])
def test_sentence_has_the_length_asked_for(n, lexicon):
    for seed in range(5):
        s = lexicon.sentence(n, random.Random(seed))
        assert len(lexicon.sentence_ipa(s)) == n
        assert s == s.lower() and s.endswith(".") and "  " not in s


def test_word_list_is_what_the_server_phonemizes(lexicon):
    """The golden table against the program's text stage: a change of the
    phonemizer for these words has to show here."""
    from sonata_tpu.models.config import default_phoneme_id_map
    from sonata_tpu.text.phonemizer import RuleG2PBackend, text_to_phonemes

    rng = random.Random(5)
    sentences = [lexicon.sentence(n, rng) for n in (34, 50, 75)]
    got = list(text_to_phonemes(" ".join(sentences),
                                backend=RuleG2PBackend()))
    assert got == [lexicon.sentence_ipa(s) for s in sentences]
    id_map = default_phoneme_id_map()
    for s in sentences:
        ids = textgen.text_to_ids(lexicon, s, id_map)
        assert len(ids) == 2 * len(lexicon.sentence_ipa(s)) + 2


@pytest.mark.parametrize("values,q,want", [
    ([5, 1, 3, 2, 4], 50, 3), ([5, 1, 3, 2, 4], 95, 5),
    (list(range(1, 101)), 95, 95), ([7], 95, 7), ([], 50, None)])
def test_percentile(values, q, want):
    assert stats.percentile(values, q) == want


def test_spread_is_the_interquartile_share_of_the_median():
    values = [100, 101, 102, 103, 104, 105]
    import statistics

    q1, _, q3 = statistics.quantiles(values, n=4)
    assert stats.spread(values) == pytest.approx(
        (q3 - q1) / statistics.median(values))


def test_stream_times_from_when_the_request_was_due():
    t = stats.stream_times(10.0, [10.4, 10.5, 10.9])
    assert t["ttfb"] == pytest.approx(0.4)
    assert t["gaps"] == pytest.approx([0.1, 0.4])
    assert stats.stream_times(10.0, [])["ttfb"] == math.inf


@pytest.mark.parametrize("rows,want", [
    ([{"rate": 2, "failed": 0, "backlog_mid": 0, "backlog_end": 1},
      {"rate": 4, "failed": 0, "backlog_mid": 1, "backlog_end": 1},
      {"rate": 8, "failed": 0, "backlog_mid": 3, "backlog_end": 9}], 4),
    ([{"rate": 2, "failed": 1, "backlog_mid": 0, "backlog_end": 0}], 0.0)])
def test_knee_is_the_last_rate_without_refusal_or_growing_backlog(rows, want):
    assert stats.knee(rows) == want


def test_wire_messages_match_the_servers_codec():
    from sonata_tpu.frontends import grpc_messages as pb

    got = pb.Utterance.decode(wire.utterance("v1", "hello there.",
                                             "PARALLEL"))
    assert (got.voice_id, got.text) == ("v1", "hello there.")
    assert got.synthesis_mode == pb.SynthesisMode.PARALLEL
    opts = pb.VoiceSynthesisOptions.decode(wire.synthesis_options(
        "v1", speaker="17", noise_scale=0.0)).synthesis_options
    # a zero is on the wire (the server reads presence), an unset field not
    assert (opts.speaker, opts.noise_scale, opts.noise_w) == ("17", 0.0,
                                                              None)
    assert pb.VoicePath.decode(wire.voice_path("/a/b.json")).config_path \
        == "/a/b.json"
    msg = pb.SynthesisResult(wav_samples=b"\x01\x02\x03\x04", rtf=0.5)
    assert wire.wav_samples(msg.encode()) == b"\x01\x02\x03\x04"


def test_costs_of_a_dispatch_and_unknown_device():
    c = costs.full_fn_cost(voicegen.MODEL_DEFAULTS, 16, 192, 768)
    # XLA's own count for the compiled program at this shape: 7.93 TFLOP
    assert c["ops"] == pytest.approx(7.93e12, rel=0.05)
    peak = costs.peaks("TPU v5 lite")
    assert costs.roofline(c, peak)["bound"] == "bytes"
    assert c["bytes"] / peak["bytes_per_s"] > c["ops"] / peak["flops_per_s"]
    with pytest.raises(KeyError):
        costs.peaks("TPU v9 imaginary")


def test_trace_reduction_on_a_recorded_trace():
    events = json.loads((DATA / "trace_events.json").read_text())
    ops = [e for e in events if e["line"] == trace.OPS_LINE]
    lo = min(e["start_ns"] for e in ops)
    hi = max(e["start_ns"] + e["dur_ns"] for e in ops)
    spans = [("dispatch", lo, (lo + hi) / 2)]
    got = trace.reduce_events(events, spans)
    assert 0 < got["busy_s"] <= got["window_s"] == pytest.approx(
        (hi - lo) / 1e9)
    assert len(got["device_ops"]) <= 10 and got["device_ops"][0][1] > 0
    assert sum(s for _, s in got["device_ops"]) <= got["busy_s"] * 1.0001
    idle = sum(s for _, s in got["idle_gaps"])
    assert idle <= got["window_s"] - got["busy_s"] + 1e-9
    assert {n for n, _ in got["idle_gaps"]} <= {"dispatch", "unattributed"}
    assert got["modules"] and all(m["dur_ns"] > 0 for m in got["modules"])
    assert trace.reduce_events([e for e in events
                                if not e["plane"].startswith("/device")]) \
        == {}


def test_union_and_gaps():
    assert trace.union_length([(0, 2), (1, 3), (5, 6)]) == 4
    assert trace.gaps([(1, 2), (4, 5)], 0, 6) == [(0, 1), (2, 4), (5, 6)]


@pytest.mark.parametrize("entry", BENCHMARK["workloads"],
                         ids=lambda w: w["name"])
def test_every_cell_finds_its_files_by_name(entry):
    from perfbench import run

    cell = run.load_cell(BENCHMARK, entry["name"], ROOT)
    assert cell["config"]["name"] == entry["config"]
    assert any(m["name"] == "setup_s" for m in cell["end_to_end"])
    assert len(cell["end_to_end"]) >= 2 and cell["per_layer"]
    for m in cell["end_to_end"] + cell["per_layer"]:
        assert NAME.match(m["name"]) and re.match(r"^[A-Za-z0-9_/%.\-]{1,16}$",
                                                  m["unit"])
        if m["name"] != "setup_s":
            assert callable(parts.load_reader(ROOT, cell["paths"],
                                              m["name"]))
    assert len(entry["why"]) <= 200 and entry["chips"] in (1, 4)


@pytest.mark.parametrize("file", sorted(
    (ROOT / "perfbench" / "configs").glob("*.json")), ids=lambda p: p.stem)
def test_configuration_files_state_what_is_run(file):
    """Every configuration file kept with the benchmark, the one whose
    cell waits under Open questions too."""
    config = json.loads(file.read_text())
    entry = next((c for c in BENCHMARK["configs"]
                  if c["name"] == config["name"]),
                 {"source": config["source"], "reduced": []})
    assert config["source"] == entry["source"]
    dims = voicegen.model_dims(config["voice"])
    # published widths of Piper's "high" VITS: no width is cut
    assert (dims["hidden_channels"], dims["filter_channels"],
            dims["upsample_initial_channel"], dims["n_layers"]) == (
        192, 768, 512, 6)
    assert entry["reduced"] == config["reduced"] == []
    # Piper's published inference scales, noise and all
    assert config["voice"]["inference"] == {
        "noise_scale": 0.667, "length_scale": 1.0, "noise_w": 0.8}
    assert "SONATA_WARMUP_LATTICE" in config["server"]["env"]


def test_the_paragraph_list_is_the_one_its_description_draws():
    import importlib.util

    spec = importlib.util.spec_from_file_location(
        "draw_paragraphs", ROOT / "perfbench/traffic/draw_paragraphs.py")
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    traffic = json.loads((ROOT / "perfbench/traffic/batch.paragraph.json")
                         .read_text())
    assert module.draw() == traffic["paragraphs"]


@pytest.mark.parametrize("estimate", [3.3, 3.5, 3.75, 4.0, 4.39, 4.9])
def test_no_estimate_takes_a_paragraph_to_a_shape_warm_up_has_not_run(
        estimate):
    """The stock path's shape of a paragraph, after ``PiperVoice``'s
    planner and frame-budget estimator (buckets copied from
    ``sonata_tpu/utils/buckets.py``): one group of 8 rows, text bucket
    192, frame bucket 768 or 1024 for any estimate of frames per id the
    running maximum can hold, and both buckets populated by the list."""
    text_buckets = (16, 32, 64, 96, 128, 192, 256, 384, 512)
    frame_buckets = (64, 128, 256, 384, 512, 768, 1024, 1536, 2048)

    def bucket(n, buckets):
        return next(b for b in buckets if n <= b)

    traffic = json.loads((ROOT / "perfbench/traffic/batch.paragraph.json")
                         .read_text())
    seen = collections.Counter()
    for paragraph in traffic["paragraphs"]:
        ids = sorted(2 * c + 2 for c in paragraph)
        assert len(ids) == 8
        # no row's text bucket is over twice the shortest row's: one group
        assert bucket(ids[-1], text_buckets) <= 2 * bucket(ids[0],
                                                           text_buckets)
        assert bucket(ids[-1], text_buckets) == 192
        seen[bucket(int(ids[-1] * estimate * 1.08), frame_buckets)] += 1
    assert set(seen) <= {768, 1024}
    if estimate <= 4.39:
        assert seen[768] >= 48
    if estimate >= 3.75:
        assert seen[1024] >= 1


def test_estimator_replay_budgets_on_start_and_observes_on_end():
    """Three requests one after the other: the first is budgeted from the
    prior (2.5 frames per id) and overflows; the second from the first's
    ratio x 1.15; the third, after a low draw, from the decayed maximum."""
    paragraphs = [[40, 95], [40, 79]]           # longest rows: 192, 160 ids
    hop = 256

    def rec(seq, para, t, frames):
        return {"seq": seq, "paragraph": para, "ok": True, "t_start": t,
                "t_end": t + 0.5, "samples": [100 * hop, frames * hop]}

    records = [rec(0, 0, 0.0, 672), rec(1, 0, 1.0, 600), rec(2, 1, 2.0, 500)]
    got = shapes.replay_estimator(records, paragraphs, hop, 0.0, 10.0)
    # 192 x 2.5 x 1.08 = 518 -> 768, 672 fit; then fpi 3.5 x 1.15 = 4.025:
    # 192 x 4.025 x 1.08 = 834 -> 1024; then max(4.025 x 0.995, 3.125):
    # 160 x 4.005 x 1.08 = 692 -> 768
    assert got["frame_buckets"] == {"768": 2, "1024": 1}
    assert got["overflow_retries"] == 0
    lo, mean, hi = got["frames_per_id_estimate"]
    assert hi == pytest.approx(4.025) and lo == pytest.approx(4.025 * 0.995
                                                              ** 2)
    clipped = shapes.replay_estimator([rec(0, 0, 0.0, 800)], paragraphs,
                                      hop, 0.0, 10.0)
    assert clipped == {"frame_buckets": {"1024": 1}, "overflow_retries": 1,
                       "frames_per_id_estimate": [pytest.approx(
                           800 / 192 * 1.15)] * 3}


def test_memory_peaks_come_from_the_server_childs_own_dump(tmp_path):
    s = object.__new__(server.Server)
    s.log_path = tmp_path / "server.log"
    s.log_path.write_text("")
    s.memory_path = tmp_path / "memory_stats.json"
    with pytest.raises(RuntimeError, match="no memory counters"):
        s.memory_peaks()
    s.memory_path.write_text(json.dumps([{"device": "cpu:0",
                                          "stats": None}]))
    assert s.memory_peaks() is None
    s.memory_path.write_text(json.dumps([{"device": "TPU_0", "stats": {
        "peak_bytes_in_use": 530222592, "peak_bytes_reserved": 7144698661,
        "bytes_limit": 16e9}}]))
    assert s.memory_peaks() == [{"device": "TPU_0", "in_use": 530222592,
                                 "reserved": 7144698661}]
    s.memory_path.write_text(json.dumps([{"device": "TPU_0", "stats": {
        "peak_bytes_in_use": 1}}]))
    with pytest.raises(KeyError):       # a counter short: no quiet fallback
        s.memory_peaks()
