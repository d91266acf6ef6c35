"""The one record per device program: what the stock path's ``dispatch``
span carries, the always-on counters beside it, the scope's accounting
reached once whichever caller it was, the program's spans inside a
profiler capture, and the stage names in the compiled program."""

from __future__ import annotations

import json
import threading
import urllib.request

import jax
import numpy as np
import pytest

from sonata_tpu.models import from_config_path, vits
from sonata_tpu.serving import MetricsRegistry, ServingRuntime, tracing
from sonata_tpu.serving import scope as scope_mod
from sonata_tpu.serving.scope import Scope
from sonata_tpu.synth import SpeechSynthesizer
from sonata_tpu.synth.scheduler import BatchScheduler
from sonata_tpu.utils import profiling
from sonata_tpu.utils.buckets import FRAME_BUCKETS, bucket_for
from tools import profile_report

from voices import tiny_voice, write_tiny_voice

TEXT = ("Hello world. This is a longer sentence for the test. Short. "
        "One more sentence of middling length.")
GROUP_FIELDS = {"batch_bucket", "text_bucket", "frame_bucket", "rows",
                "padding_rows", "padding_ratio", "compile", "frames_needed",
                "frames_budget", "frames_per_id", "decode_fold",
                "upload_bytes", "enqueue_ms", "launch_ms", "fetch_wait_ms",
                "epilogue_ms"}


def frames_computed(groups) -> int:
    """Padded batch x frames of every program the groups ran."""
    total = 0
    for g in groups:
        total += g["batch_bucket"] * g["frame_bucket"]
        if g.get("overflow"):
            total += g["batch_bucket"] * g["retry_bucket"]
    return total


def stats_delta(before: dict, after: dict) -> dict:
    out = {k: after[k] - before[k] for k in ("groups", "overflow_retries")}
    out["frames"] = {p: after["frames"][p] - before["frames"][p]
                     for p in tracing.FRAME_PARTS}
    return out


@pytest.fixture()
def scope():
    sc = Scope(slos="error_rate:0.01")
    scope_mod.install(sc)
    try:
        yield sc
    finally:
        scope_mod.uninstall(sc)


def traced(tracer, fn, **attrs):
    with tracer.trace_request("req", **attrs) as tr:
        out = fn()
    return tr, out


def dispatch_spans(trace) -> list:
    return [s for s in trace.spans_snapshot() if s.name == "dispatch"]


# ---------------------------------------------------------------------------
# the split of a program's frames (pure arithmetic)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("group,expect", [
    # the cell's usual program: longest row 540, budget 640, bucket 768
    (dict(batch_bucket=8, rows=8, frame_bucket=768, frames_budget=640,
          frames_needed=[540, 500, 450, 420, 400, 380, 350, 240]),
     dict(served=3280, ragged=1040, headroom=800, bucket=1024,
          dummy_rows=0, retried=0)),
    # the estimate fell short of the longest row but the bucket held it
    (dict(batch_bucket=4, rows=3, frame_bucket=128, frames_budget=90,
          frames_needed=[100, 60, 50]),
     dict(served=210, ragged=90, headroom=0, bucket=84, dummy_rows=128,
          retried=0)),
    # a budget above the bucket (the ladder's top) is clipped to it
    (dict(batch_bucket=1, rows=1, frame_bucket=4096, frames_budget=5000,
          frames_needed=[3000]),
     dict(served=3000, ragged=0, headroom=1096, bucket=0, dummy_rows=0,
          retried=0)),
    # overflow: the clipped program counts whole, the rerun has no headroom
    (dict(batch_bucket=2, rows=2, frame_bucket=64, frames_budget=40,
          frames_needed=[100, 30], overflow=True, retry_bucket=128),
     dict(served=130, ragged=70, headroom=0, bucket=56, dummy_rows=0,
          retried=128)),
])
def test_frame_parts_sum_to_the_padded_frames(group, expect):
    parts = tracing.frame_parts(group)
    assert parts == expect
    assert sum(parts.values()) == frames_computed([group])
    assert all(v >= 0 for v in parts.values())


# ---------------------------------------------------------------------------
# the stock path
# ---------------------------------------------------------------------------

def test_stock_path_dispatch_span_says_what_it_ran():
    voice = tiny_voice()
    voice.scope_voice = "model-label"
    synth = SpeechSynthesizer(voice)
    tracer = tracing.Tracer(enabled=True, log_sink="0")
    before = tracing.program_stats().snapshot()
    trace, audios = traced(
        tracer, lambda: list(synth.synthesize_parallel(TEXT)),
        request_id="stock-1", voice="wire-voice")
    (span,) = dispatch_spans(trace)
    attrs = span.attrs
    assert attrs["sentences"] == 4 and attrs["groups"] == len(
        attrs["device_groups"])
    assert attrs["dispatch_id"] and attrs["request_ids"] == ["stock-1"]
    assert attrs["voice"] == "wire-voice"    # the trace's, not the model's
    for g in attrs["device_groups"]:
        assert GROUP_FIELDS <= set(g)
        assert g["rows"] == len(g["frames_needed"])
        assert g["frames_budget"] <= g["frame_bucket"] or g.get("overflow")
        assert g["frame_bucket"] in FRAME_BUCKETS
        assert g["enqueue_ms"] >= g["launch_ms"] > 0
        assert g["fetch_wait_ms"] > 0
    # headline fields as annotate_dispatch_group aggregates them
    first = attrs["device_groups"][0]
    for k in ("batch_bucket", "text_bucket", "frame_bucket", "rows"):
        assert attrs[k] == first[k]
    assert attrs["compile"] in ("cold", "cached")
    # the served durations: every row's frames are its audio over the hop
    hop = voice.hp.hop_length
    needed = sorted(f for g in attrs["device_groups"]
                    for f in g["frames_needed"])
    assert needed == sorted(len(a.samples.data) // hop for a in audios)
    json.dumps(trace.to_dict())             # /debug/traces can serve it
    # the counters moved by exactly these programs
    delta = stats_delta(before, tracing.program_stats().snapshot())
    assert delta["groups"] == len(attrs["device_groups"])
    assert sum(delta["frames"].values()) == frames_computed(
        attrs["device_groups"])
    assert delta["frames"]["served"] == sum(needed)
    assert tracing.program_stats().frames_per_id("model-label") == \
        attrs["device_groups"][-1]["frames_per_id"]


def test_a_program_uploads_its_arguments_not_the_weights(tmp_path):
    """``upload_bytes`` of a voice loaded from an ``.npz`` (numpy arrays
    out of ``np.load``, where ``tiny_voice()`` starts from device arrays):
    the ids, lengths, scales and key of each program, and the series
    beside the host seconds advances by the same."""
    voice = from_config_path(write_tiny_voice(tmp_path))
    weights = sum(leaf.nbytes for leaf in
                  jax.tree_util.tree_leaves(voice.params))
    assert weights > 64 * 1024
    registry = MetricsRegistry()
    tracing.program_stats().bind_metrics(registry)

    def series() -> float:
        (line,) = [l for l in registry.render().splitlines()
                   if l.startswith("sonata_dispatch_upload_bytes_total ")]
        return float(line.split()[1])

    tracer = tracing.Tracer(enabled=True, log_sink="0")
    before = series()
    trace, _ = traced(
        tracer, lambda: voice.speak_batch(list(voice.phonemize_text(TEXT))),
        request_id="upload-1")
    (span,) = dispatch_spans(trace)
    groups = span.attrs["device_groups"]
    for g in groups:
        # b x t ids, and per row a length and three scales, and the key
        assert g["upload_bytes"] == (
            g["batch_bucket"] * (g["text_bucket"] + 4) * 4 + 8)
        assert g["upload_bytes"] < 64 * 1024
    assert series() - before == sum(g["upload_bytes"] for g in groups)


def test_counters_count_without_a_trace():
    voice = tiny_voice()
    before = tracing.program_stats().snapshot()
    audios = voice.speak_batch(list(voice.phonemize_text(TEXT)))
    delta = stats_delta(before, tracing.program_stats().snapshot())
    assert delta["groups"] >= 1
    hop = voice.hp.hop_length
    assert delta["frames"]["served"] == sum(
        len(a.samples.data) // hop for a in audios)


def test_forced_overflow_is_one_retry_and_the_same_audio():
    sentence = list(tiny_voice().phonemize_text(
        "This is a longer sentence for the test."))
    probe = tiny_voice()
    frames = len(probe.speak_batch(sentence)[0].samples.data) \
        // probe.hp.hop_length
    ids = len(probe._encode_phonemes(sentence[0]))
    fits = bucket_for(frames, FRAME_BUCKETS)
    assert fits > FRAME_BUCKETS[0]

    def speak(frames_per_id):
        voice = tiny_voice()        # same seed: same first duration draw
        voice.frame_estimator.frames_per_id = frames_per_id
        voice.frame_estimator.observed = True
        tracer = tracing.Tracer(enabled=True, log_sink="0")
        before = tracing.program_stats().snapshot()
        trace, audios = traced(tracer, lambda: voice.speak_batch(sentence))
        delta = stats_delta(before, tracing.program_stats().snapshot())
        (span,) = dispatch_spans(trace)
        return audios[0].samples.data, span.attrs, delta

    # budgeted exactly: the bucket that fits, first time
    sound, attrs, delta = speak(frames / ids / 1.08 * 1.001)
    (g,) = attrs["device_groups"]
    assert g["frame_bucket"] == fits and "overflow" not in g
    assert delta["overflow_retries"] == 0 and delta["frames"]["retried"] == 0
    # budgeted far too low: clipped, rerun once in the bucket that fits
    clipped, attrs, delta = speak(0.05)
    (g,) = attrs["device_groups"]
    assert g["overflow"] is True and g["retry_bucket"] == fits
    assert g["frame_bucket"] < fits and attrs["overflow"] is True
    assert g["frames_needed"] == [frames]
    assert delta["overflow_retries"] == 1 and delta["groups"] == 1
    assert delta["frames"]["retried"] == g["batch_bucket"] * g["frame_bucket"]
    assert delta["frames"]["headroom"] == 0
    assert sum(delta["frames"].values()) == frames_computed([g])
    np.testing.assert_array_equal(clipped, sound)


def test_the_group_says_how_its_decoder_folded():
    """Static per compiled shape, so recorded where shapes are: the tiny
    voice's two stages have 32 and 16 channels."""
    voice = tiny_voice()
    tracer = tracing.Tracer(enabled=True, log_sink="0")
    trace, _ = traced(tracer, lambda: voice.speak_batch(
        list(voice.phonemize_text(TEXT))))
    (span,) = dispatch_spans(trace)
    assert "decode_fold" not in span.attrs      # not a headline field
    for g in span.attrs["device_groups"]:
        assert g["decode_fold"] == [4, 8]
        assert g["decode_fold"] == vits.decode_fold(
            voice.params["dec"], voice.hp, g["frame_bucket"])


def test_a_compile_after_warmup_counts_on_the_stock_path(scope):
    voice = tiny_voice()
    voice.scope_voice = "v"
    sentences = list(voice.phonemize_text(TEXT))
    voice.speak_batch(sentences[:1])            # the boot's one utterance
    assert scope.dispatches_total == 1 and scope.cold_compiles_total == 1
    assert scope.runtime_cold_compiles("v") == 0
    scope.mark_warmup_complete(voices=["v"])
    # untraced: the model's own label names the voice
    voice.speak_batch(sentences)                # shapes warm-up never ran
    assert scope.dispatches_total == 2
    cold = scope.runtime_cold_compiles("v")
    assert cold >= 1
    voice.speak_batch(sentences)                # now cached
    assert scope.dispatches_total == 3
    assert scope.runtime_cold_compiles("v") == cold
    rows = scope.buckets_snapshot()["buckets"]
    assert sum(r["dispatches"] for r in rows) == 3
    assert all(r["frame_bucket"] in FRAME_BUCKETS for r in rows)


def test_the_scheduler_counts_its_dispatch_once(scope):
    voice = tiny_voice()
    sched = BatchScheduler(voice, max_batch=4, max_wait_ms=50.0,
                           trace_attrs={"voice": "sched-voice"})
    tracer = tracing.Tracer(enabled=True, log_sink="0")
    sentence = list(voice.phonemize_text("Hello world."))[0]
    before = tracing.program_stats().snapshot()
    try:
        trace, audio = traced(
            tracer, lambda: sched.submit(sentence).result(120.0),
            request_id="sched-1")
    finally:
        sched.shutdown()
    # the scheduler's channel is reused: one dispatch, one group
    assert scope.dispatches_total == 1
    delta = stats_delta(before, tracing.program_stats().snapshot())
    assert delta["groups"] == 1
    (span,) = dispatch_spans(trace)
    attrs = span.attrs
    # what the scheduler's span carried before, and the group's new fields
    assert attrs["batch_size"] == 1 and attrs["request_ids"] == ["sched-1"]
    assert attrs["voice"] == "sched-voice" and attrs["compile"] in (
        "cold", "cached")
    assert {"batch_bucket", "text_bucket", "frame_bucket", "rows",
            "padding_rows", "padding_ratio", "dispatch_id"} <= set(attrs)
    assert "sentences" not in attrs     # the model's own span stays a no-op
    (g,) = attrs["device_groups"]
    assert GROUP_FIELDS <= set(g)
    assert g["frames_needed"] == [len(audio.samples.data)
                                  // voice.hp.hop_length]


# ---------------------------------------------------------------------------
# /metrics
# ---------------------------------------------------------------------------

def test_metrics_export_the_program_counters():
    runtime = ServingRuntime(registry=MetricsRegistry())
    try:
        voice = tiny_voice()
        voice.scope_voice = "metrics-voice"
        runtime.register_voice("metrics-voice")
        voice.speak_batch(list(voice.phonemize_text("Hello world.")))
        text = runtime.registry.render()
    finally:
        runtime.unregister_voice("metrics-voice")
        runtime.close()
    snap = tracing.program_stats().snapshot()
    for part in tracing.FRAME_PARTS:
        assert (f'sonata_dispatch_frames_total{{part="{part}"}} '
                f'{snap["frames"][part]}') in text
    for phase in tracing.HOST_PHASES:
        assert f'sonata_dispatch_host_seconds_total{{phase="{phase}"}} ' \
            in text
    assert f"sonata_dispatch_groups_total {snap['groups']}" in text
    assert "sonata_dispatch_overflow_retries_total " in text
    assert ('sonata_frame_estimator_frames_per_id{voice="metrics-voice"} '
            in text)
    assert "sonata_frame_estimator_frames_per_id" not in \
        runtime.registry.render()       # unregistered with the voice


# ---------------------------------------------------------------------------
# the profiler's trace
# ---------------------------------------------------------------------------

def test_a_capture_holds_the_programs_spans_with_their_ids(monkeypatch):
    voice = tiny_voice()
    synth = SpeechSynthesizer(voice)
    list(synth.synthesize_parallel(TEXT))       # compile outside the capture
    tracer = tracing.Tracer(enabled=True, log_sink="0")
    request_done = threading.Event()
    # the capture lasts as long as the request, not a fixed time
    monkeypatch.setattr(profiling.time, "sleep",
                        lambda s: request_done.wait(120.0))
    result = {}
    capture = threading.Thread(
        target=lambda: result.update(profiling.capture_profile(1.0)))
    assert isinstance(tracing.annotation("enqueue"),
                      type(profiling.NO_ANNOTATION))   # no capture: no-op
    capture.start()
    try:
        for _ in range(2000):
            if profiling._capturing:
                break
            threading.Event().wait(0.005)
        assert profiling._capturing
        trace, _ = traced(tracer,
                          lambda: list(synth.synthesize_parallel(TEXT)),
                          request_id="cap-1")
    finally:
        request_done.set()
        capture.join(120.0)
    assert not capture.is_alive() and not profiling._capturing
    (span,) = dispatch_spans(trace)
    notes = profile_report.annotations(profile_report.load(
        result["log_dir"]))
    names = {e["name"] for e in notes}
    assert {"sonata:phonemize", "sonata:encode-ids", "sonata:enqueue",
            "sonata:fetch", "sonata:epilogue"} <= names
    for e in notes:
        assert e["ids"]["request_id"] == "cap-1"
        if e["name"] in ("sonata:enqueue", "sonata:fetch",
                         "sonata:epilogue"):
            assert e["ids"]["dispatch_id"] == span.attrs["dispatch_id"]
    # one of each phase per device group, never one per row
    groups = len(span.attrs["device_groups"])
    for phase in ("enqueue", "fetch", "epilogue"):
        assert sum(e["name"] == "sonata:" + phase for e in notes) == groups
    anchors = result["anchors"]
    assert [*anchors] == ["start_called", "start_returned", "stop_called",
                          "stop_returned"]
    walls = [a["wall"] for a in anchors.values()]
    assert walls == sorted(walls)


def test_debug_profile_returns_the_anchors():
    from sonata_tpu.serving.metrics import start_http_server

    server = start_http_server(MetricsRegistry(), port=0,
                               tracer=tracing.Tracer(enabled=True))
    try:
        url = f"http://127.0.0.1:{server.port}/debug/profile?seconds=0.1"
        with urllib.request.urlopen(url, timeout=120.0) as resp:
            doc = json.loads(resp.read())
    finally:
        server.stop()
    assert {"log_dir", "seconds", "view", "anchors"} <= set(doc)
    # the harness writes wall_start / wall_end over the response
    assert not {"wall_start", "wall_end"} & set(doc)
    for name in ("start_called", "start_returned", "stop_called",
                 "stop_returned"):
        assert set(doc["anchors"][name]) == {"wall", "monotonic"}
    a = doc["anchors"]
    assert a["start_called"]["monotonic"] <= a["start_returned"][
        "monotonic"] <= a["stop_called"]["monotonic"] - 0.1


# ---------------------------------------------------------------------------
# stage names in the compiled program
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def full_program_text():
    voice = tiny_voice()
    b, t, f = 1, 16, 64
    args = voice._dummy_full_args(b, t)
    return voice._full_fn(b, t, f).lower(*args).as_text(debug_info=True)


@pytest.mark.parametrize("path", [
    "encode_text", "encode_text/duration_predictor", "acoustics",
    "acoustics/flow_reverse", "decode/pre", "decode/ups1", "decode/ups2",
    "decode/post", "epilogue"])
def test_the_program_names_its_stages(full_program_text, path):
    assert f"/{path}/" in full_program_text
