"""The readers of the compile events and of the step loop's turns: on built
``run`` objects (the arithmetic, and a program from before either), their
entries in ``BENCHMARK.json``, and once on a real traced run of the tiny
unit-voice cell on the CPU, over a benchmark file of this test's own."""

import json
from pathlib import Path

import pytest

from perfbench import run
from perfbench.harness import parts

ROOT = Path(__file__).resolve().parent.parent.parent
BENCH = Path(__file__).resolve().parent / "data" / \
    "loop-compile-benchmark.json"
ALL = ["lessac-high.batch.paragraph", "lfm2-24b-a2b.batch.sentence",
       "sdar-30b-a3b.batch.sentence",
       "nemotron-3-nano-30b-a3b.batch.sentence256"]
#: name -> (source, moves, workloads)
NEW = {
    "warmup.compile_s_before_window": ("program_counter", "setup_s", ALL),
    "warmup.cache_load_share_before_window": ("program_counter", "setup_s",
                                              ALL),
    "warmup.compiles_after_ready": ("program_counter", "audio_s_per_s", ALL),
    "loop.device_wait_share": ("program_span", "audio_s_per_s", ALL[1:]),
    "loop.turn_ms_max": ("program_span", "audio_s_per_s", ALL[1:]),
}


def reader(name):
    return parts.load_reader(ROOT, ["perfbench", "tests/perfbench"], name)


def page(compiles: dict) -> dict:
    """``/metrics`` as the harness parses it: ``{(program, phase, cache,
    stage): (count, seconds)}``, with a label the registry might add."""
    out = {"sonata_requests_total": 7.0}
    for (program, phase, cache, stage), (n, s) in compiles.items():
        labels = (f'cache="{cache}",node="a",phase="{phase}",'
                  f'program="{program}",stage="{stage}"')
        out[f"sonata_compile_total{{{labels}}}"] = float(n)
        out[f"sonata_compile_seconds_total{{{labels}}}"] = float(s)
    return out


START = {("lfm2_step", "trace", "off", "warmup"): (1, 2.0),
         ("lfm2_step", "lower", "off", "warmup"): (1, 1.5),
         ("lfm2_step", "backend", "miss", "warmup"): (1, 30.0),
         ("lfm2_prefill", "trace", "off", "warmup"): (7, 7.0),
         ("lfm2_prefill", "lower", "off", "warmup"): (7, 3.5),
         ("lfm2_prefill", "backend", "hit", "warmup"): (6, 1.0),
         ("lfm2_prefill", "backend", "miss", "warmup"): (1, 12.0),
         ("convert_element_type", "backend", "off", "warmup"): (2, 0.25)}
AFTER = {**START,
         ("convert_element_type", "trace", "off", "serving"): (1, 0.001),
         ("convert_element_type", "backend", "off", "serving"): (1, 0.02),
         ("unit_vocode", "backend", "hit", "serving"): (2, 0.4)}


def group(**attrs) -> dict:
    return {"name": "dispatch", "start": 0.0, "end": 1.0,
            "attrs": dict({"kind": "step", "steps": 32,
                           "host_ms": {"launch": 40.0, "admit": 30.0,
                                       "retire": 18.0}}, **attrs)}


SPANS = [
    group(wall_ms=500.0, device_wait_ms=400.0, record_ms=8.0, other_ms=4.0,
          turn_ms_max=41.5, turn_max_phase="admit", turn_max_step=17),
    group(wall_ms=700.0, device_wait_ms=500.0, record_ms=90.0, other_ms=22.0,
          turn_ms_max=97.25, turn_max_phase="record", turn_max_step=64),
    # a prefill's span and a span of another name carry no turns
    {"name": "dispatch", "start": 0.0, "end": 1.0,
     "attrs": {"kind": "prefill", "tokens": 90}},
    {"name": "phonemize", "start": 0.0, "end": 1.0, "attrs": {}}]
BUILT = {"metrics_before": page(START), "metrics_after": page(AFTER),
         "spans": SPANS}
#: a program from before the events and the turns: the parent's
OLD = {"metrics_before": {"sonata_requests_total": 7.0,
                          'sonata_ar_host_seconds_total{phase="admit"}': 1.0},
       "metrics_after": {"sonata_requests_total": 9.0},
       "spans": [group()]}


@pytest.mark.parametrize("name,expect", [
    ("warmup.compile_s_before_window", 57.25),
    ("warmup.cache_load_share_before_window", 60.0),    # 6 of 10
    ("warmup.compiles_after_ready", 3.0),
    ("loop.device_wait_share", 75.0),                   # 900 of 1200 ms
    ("loop.turn_ms_max", 97.25),
])
def test_reader_arithmetic_on_a_recorded_run(name, expect):
    assert reader(name)(BUILT) == pytest.approx(expect, abs=1e-9)


@pytest.mark.parametrize("name", sorted(NEW))
def test_a_program_without_the_events_reads_as_nothing(name):
    assert reader(name)(OLD) is None
    assert reader(name)(dict(OLD, spans=None)) is None


def test_a_quiet_window_reads_no_compile_and_not_nothing():
    quiet = dict(BUILT, metrics_after=BUILT["metrics_before"])
    assert reader("warmup.compiles_after_ready")(quiet) == 0.0


def test_the_old_host_reader_reads_as_before_beside_the_new_attributes():
    assert reader("ar.host_ms_per_step.sentence")(BUILT) == pytest.approx(
        2 * 88.0 / 64)


@pytest.mark.parametrize("name", sorted(NEW))
def test_every_new_metric_is_declared_with_a_reader_and_its_cells(name):
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    (m,) = [m for m in bench["per_layer"] if m["name"] == name]
    source, moves, workloads = NEW[name]
    assert (m["source"], m["moves"], m["workloads"]) == (source, moves,
                                                         workloads)
    assert set(m) == {"name", "unit", "better", "source", "layer", "moves",
                      "workloads"}
    assert m["layer"] in {x["layer"] for x in bench["per_layer"][:-5]}
    assert {w["name"] for w in bench["workloads"]} >= set(workloads)
    assert callable(reader(name))
    # appended: the five are the list's end, in the issue's order
    assert [x["name"] for x in bench["per_layer"][-5:]] == list(NEW)


def test_a_traced_run_of_the_tiny_unit_cell_reports_them():
    out = run.run_cell("lfm2-tiny.sentence", 3000000019, 2.0, True,
                       benchmark_file=BENCH, platform="cpu",
                       require_accelerator=False)
    assert out["failed"] == 0 and out["attempted"] > 0
    got = {k: v["value"] for k, v in out["metrics"].items()}
    assert set(NEW) <= set(got)
    # the start traced, lowered and compiled (or loaded) its programs
    assert got["warmup.compile_s_before_window"] > 0.0
    assert 0.0 <= got["warmup.cache_load_share_before_window"] <= 100.0
    assert got["warmup.compiles_after_ready"] >= 0.0
    assert 0.0 < got["loop.device_wait_share"] < 100.0
    assert got["loop.turn_ms_max"] > 0.0
    assert got["ar.host_ms_per_step.sentence"] > 0.0
