"""Every XLA compile is an event of ``serving/tracing.py``: one record a
phase with the program's name and the thread that paid, counted by stage,
handed to the record of the work that waited for it, and a ``compile`` span
of the request where one is current."""

from __future__ import annotations

import json
import threading
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from perfbench.harness import lfm2gen
from sonata_tpu.models import from_config_path
from sonata_tpu.serving import MetricsRegistry, tracing
from sonata_tpu.serving import scope as scope_mod
from sonata_tpu.serving.metrics import parse_prometheus_text
from sonata_tpu.serving.scope import Scope
from sonata_tpu.synth import SpeechSynthesizer

from voices import tiny_voice

ROOT = Path(__file__).resolve().parent.parent
PHASES = ("trace", "lower", "backend")


def of(paid: list, program: str) -> list:
    return [r for r in paid if r["program"] == program]


def test_a_jitted_function_gives_one_record_a_phase_once_a_shape():
    @jax.jit
    def compile_events_probe(x):
        return jnp.where(x > 0, x * 2, x + 1)     # jnp's own jits nest here

    x3, x4 = np.ones(3, np.float32), np.ones(4, np.float32)
    with tracing.compile_sink() as paid:
        compile_events_probe(x3)
        first = of(paid, "compile_events_probe")
        assert [r["phase"] for r in first] == list(PHASES)
        for r in first:
            assert r["thread"] == threading.current_thread().name
            assert r["seconds"] == pytest.approx(r["end"] - r["start"])
            assert r["seconds"] > 0
        assert [r["cache"] for r in first[:2]] == ["off", "off"]
        assert first[2]["cache"] in ("hit", "miss", "off")
        # a nested trace (``where``, ``multiply``) is its parent's time
        assert {r["program"] for r in paid} == {"compile_events_probe"}
        compile_events_probe(x3)                   # the same shape: nothing
        assert len(of(paid, "compile_events_probe")) == 3
        compile_events_probe(x4)                   # another: one more each
        assert [r["phase"] for r in of(paid, "compile_events_probe")[3:]] \
            == list(PHASES)


def test_the_records_lie_on_the_spans_clock():
    import time

    before = time.monotonic()
    with tracing.compile_sink() as paid:
        jax.jit(lambda x: x - 3)(np.ones(5, np.float32))
    after = time.monotonic()
    assert paid
    for r in paid:
        assert before - 0.05 <= r["start"] <= r["end"] <= after + 0.05


def test_a_compile_lands_in_the_record_of_its_own_thread_and_no_other():
    started, release = threading.Event(), threading.Event()
    other = {}

    def bystander():
        with tracing.compile_sink() as paid:
            started.set()
            release.wait(60.0)
            other["paid"] = list(paid)

    t = threading.Thread(target=bystander)
    t.start()
    started.wait(60.0)
    with tracing.compile_sink() as outer:
        with tracing.compile_sink() as inner:
            jax.jit(lambda x: x * 5 - 1)(np.ones(6, np.float32))
        took = tracing.launch_compile(inner)
        assert took["compile"] == "cold" and took["compile_ms"] > 0
        assert took["compiled"] == ["<lambda>"]
        assert inner == []                 # drained by whoever closed
        assert outer == []                 # the inner block took it
        assert tracing.launch_compile(inner) == {"compile": "cached"}
        assert tracing.compile_attrs(inner) == {}
    release.set()
    t.join(60.0)
    assert other["paid"] == []


def test_a_stock_dispatch_record_gains_what_compiled_under_it():
    synth = SpeechSynthesizer(tiny_voice())
    tracer = tracing.Tracer(enabled=True, log_sink="0")
    with tracer.trace_request("compile-test", request_id="ce-1") as trace:
        list(synth.synthesize_parallel("A first sentence, which compiles."))
    spans = {s.name: s for s in trace.spans_snapshot()}
    groups = spans["dispatch"].attrs["device_groups"]
    cold = [g for g in groups if g["compile"] == "cold"]
    assert cold                      # a fresh voice's first back program
    for g in cold:
        assert g["compile_ms"] > 0 and g["compiled"]
    # and the request's trace says which programs it waited for
    compiles = [s for s in trace.spans_snapshot() if s.name == "compile"]
    assert compiles and {s.attrs["phase"] for s in compiles} <= set(PHASES)
    assert all(s.attrs["program"] and s.end >= s.start for s in compiles)
    with tracer.trace_request("compile-test", request_id="ce-2") as again:
        list(synth.synthesize_parallel("A first sentence, which compiles."))
    for s in again.spans_snapshot():
        assert s.name != "compile"
        if s.name == "dispatch":
            for g in s.attrs["device_groups"]:
                assert g["compile"] == "cached" and "compile_ms" not in g


@pytest.fixture(scope="module")
def unit_voice(tmp_path_factory):
    config = json.loads(
        (ROOT / "tests/perfbench/data/lfm2-tiny.json").read_text())
    path = lfm2gen.write_tensors(tmp_path_factory.mktemp("ce_voice"), config)
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("SONATA_AR_SLOTS", "2")
        mp.setenv("SONATA_AR_POSITIONS", "256")
        voice = from_config_path(path)
    yield voice
    voice.close()


def test_a_unit_voices_first_prefill_at_a_bucket_is_cold_and_its_second_cached(
        unit_voice):
    cache = unit_voice.new_cache()
    cache, _, _, first = unit_voice.prefill(cache, 0, [3, 4, 5], 0.0)
    assert first["compile"] == "cold" and first["compile_ms"] > 0
    assert any("prefill" in name for name in first["compiled"])
    cache, _, _, second = unit_voice.prefill(cache, 1, [5, 4, 3, 2], 0.0)
    assert second["text_bucket"] == first["text_bucket"]
    assert second["compile"] == "cached"
    assert "compile_ms" not in second and "compiled" not in second
    cache, _, _, wider = unit_voice.prefill(cache, 0, list(range(1, 41)), 0.0)
    assert wider["text_bucket"] > first["text_bucket"]
    assert wider["compile"] == "cold" and wider["compile_ms"] > 0


def test_a_unit_voices_vocoder_says_the_same_of_itself(unit_voice):
    cache = unit_voice.new_cache()
    out, first = unit_voice.vocode(cache, 0, 3, 20)
    jax.block_until_ready(out)
    assert first["compile"] == "cold" and first["compile_ms"] > 0
    assert any("vocode" in name for name in first["compiled"])
    out, second = unit_voice.vocode(cache, 1, 3, 24)
    assert second["frames_bucket"] == first["frames_bucket"]
    assert second["compile"] == "cached" and "compile_ms" not in second


def test_the_stage_turns_with_the_installed_scopes_warm_up():
    stats = tracing.compile_stats()
    sc = Scope(slos="error_rate:0.01")
    scope_mod.install(sc)
    try:
        assert stats.stage == "warmup"
        jax.jit(lambda x: x + 11)(np.ones(2, np.float32))
        Scope(slos="error_rate:0.01").mark_warmup_complete()  # not installed
        assert stats.stage == "warmup"
        sc.mark_warmup_complete()
        assert stats.stage == "serving"
        jax.jit(lambda x: x + 12)(np.ones(2, np.float32))
        stages = {key[3] for key, (n, _) in stats.snapshot().items()
                  if key[0] == "<lambda>" and n}
        assert stages == {"warmup", "serving"}
        # a new runtime's scope starts its own warm-up
        scope_mod.install(Scope(slos="error_rate:0.01"))
        assert stats.stage == "warmup"
    finally:
        scope_mod.uninstall(scope_mod.installed())
        stats.stage = "warmup"


def test_the_series_render_and_parse():
    registry = MetricsRegistry()
    tracing.compile_stats().bind_metrics(registry)

    @jax.jit
    def compile_series_probe(x):
        return x * 7

    compile_series_probe(np.ones(3, np.float32))     # bound after the bind
    page = parse_prometheus_text(registry.render())
    rows = [(labels, v) for labels, v in page["sonata_compile_total"]
            if labels["program"] == "compile_series_probe"]
    assert {labels["phase"] for labels, _ in rows} == set(PHASES)
    for labels, value in rows:
        assert set(labels) == {"program", "phase", "cache", "stage"}
        assert labels["stage"] in tracing.COMPILE_STAGES
        assert value == 1
    seconds = [v for labels, v in page["sonata_compile_seconds_total"]
               if labels["program"] == "compile_series_probe"]
    assert len(seconds) == 3 and all(v > 0 for v in seconds)
    # the benchmark's own parser (one series a line, no brace in a label)
    from perfbench.harness import server

    parsed = server.parse_metrics(registry.render())
    assert any(k.startswith("sonata_compile_total{") and
               'program="compile_series_probe"' in k for k in parsed)


@pytest.mark.parametrize("fun_name,program", [
    ("jit(lfm2_step)", "lfm2_step"),
    ("convert_element_type", "convert_element_type"),
    ("jit(convert_element_type)", "convert_element_type"),
    ("pmap(f)", "f"),
    ("jit(<lambda>)", "<lambda>"),
    ('jit(a "b" {c})', "a__b___c_"),
    (None, "unknown"),
    ("jit(" + "x" * 100 + ")", "x" * 64),
])
def test_a_programs_name_is_fun_name_without_its_jit(fun_name, program):
    assert tracing._program_name(fun_name) == program


def test_the_names_counted_apart_are_bounded(monkeypatch):
    monkeypatch.setattr(tracing, "MAX_COMPILE_PROGRAMS", 2)
    stats = tracing.CompileStats()
    for name in ("a", "b", "c", "d", "a"):
        stats.record({"program": name, "phase": "backend", "cache": "miss",
                      "seconds": 0.5})
    got = {key[0]: n for key, (n, _) in stats.snapshot().items()}
    assert got == {"a": 2, "b": 1, "other": 2}
