"""The step loop's account of its own turns, on an engine of this file's own
that touches no jax (the protocol is in ``steploop.py``'s docstring): which
phase held the longest turn, what the loop spent blocked on the device, that
a group's phases sum to its wall time, what the always-on series gain, and
what the loop mirrors into a profiler capture; and how rows are admitted:
one prompt a launch where the engine's step carries arrivals, a prefill
apart a row where it does not."""

from __future__ import annotations

import contextlib
import threading
import time
import types
from pathlib import Path

import numpy as np
import pytest

from sonata_tpu.serving import MetricsRegistry, tracing
from sonata_tpu.serving import scope as scope_mod
from sonata_tpu.serving.metrics import parse_prometheus_text
from sonata_tpu.serving.scope import Scope
from sonata_tpu.synth import steploop
from sonata_tpu.utils import profiling

TURN_PHASES = ("admit", "launch", "retire", "device_wait", "record", "other")


class Load:
    """What a program hands back: on the host at ``ready_at`` (the
    ``perf_counter``'s time), and whoever reads it earlier waits."""

    def __init__(self, ready_at: float = 0.0):
        self.ready_at = ready_at

    def copy_to_host_async(self) -> None:
        pass

    def __array__(self, dtype=None, copy=None):
        time.sleep(max(self.ready_at - time.perf_counter(), 0.0))
        return np.zeros((0, 3), np.int64)


class Plan:
    block = 1

    def __init__(self, launches: int):
        self.launches = launches

    def units(self, done: int) -> int:
        return done

    def attended(self, done: int) -> int:
        return done + 1

    def commits(self, done: int) -> bool:
        return True


class Engine:
    """One unit a launch, ``launches`` of them a row; ``prefill_s`` is what
    a prefill keeps the host, ``step_s`` what a step keeps the device."""

    slots = 4
    expert_layers = ()
    block_length = 1
    denoising_steps = 1
    expert_matmul = "ragged_dot"
    attention = "einsum"
    #: a cache of no kind: nothing beyond the loop's own sums
    description = types.SimpleNamespace(
        static={}, row_sums=(), rows=[()] * 64, closed=lambda group: {},
        resident={}, prefill=lambda text_bucket: {})

    def __init__(self, launches=6, prefill_s=0.0, step_s=0.0,
                 prefill_compile=None):
        self.launches, self.prefill_s, self.step_s = (launches, prefill_s,
                                                      step_s)
        self.prefill_compile = prefill_compile or {"compile": "cached"}
        self.steps = []
        self.device_free_at = 0.0      # programs run one behind the other

    def new_cache(self):
        return {}

    def plan(self, n_ids: int, budget: int) -> Plan:
        return Plan(self.launches)

    def prefill(self, cache, slot, ids, temperature):
        time.sleep(self.prefill_s)
        return cache, None, Load(), dict(self.prefill_compile,
                                         expert_matmul="ragged_dot",
                                         attention="einsum", text_bucket=32)

    def step(self, cache, live, temperature, step_no):
        self.steps.append(step_no)
        self.device_free_at = max(self.device_free_at,
                                  time.perf_counter()) + self.step_s
        return cache, (), Load(self.device_free_at)

    def vocode(self, cache, slot, n_ids, units):
        return object(), {"batch_bucket": 1, "frames_bucket": 64,
                          "compile": "cached"}

    def wait_audio(self, out) -> None:
        pass

    def fetch_audio(self, out, units):
        return np.zeros((units,), np.float32)


def run_rows(engine: Engine, name: str, rows: int = 1) -> list:
    """Rows through a loop of ``engine``; the attributes of the step groups
    it recorded."""
    loop = steploop.StepLoop(engine, name=name)
    try:
        futures = [loop.submit([1, 2, 3], 8, 0.0) for _ in range(rows)]
        for f in futures:
            assert f.result(timeout=60.0).shape == (8,)
        deadline = time.monotonic() + 30.0
        while time.monotonic() < deadline:
            groups = [s.attrs for t in tracing.default_tracer().recent_traces()
                      if t.request_id.startswith(f"ar-steps-{name}-")
                      for s in t.spans_snapshot()
                      if s.name == "dispatch" and s.attrs.get("kind") == "step"]
            if sum(g["steps"] for g in groups) == len(engine.steps) > 0:
                return groups
            time.sleep(0.01)
        raise AssertionError("the loop never handed its groups on")
    finally:
        loop.close()


@pytest.mark.parametrize("engine,phase", [
    (dict(prefill_s=0.08), "admit"),
    (dict(step_s=0.04), "device_wait"),
], ids=["a-slow-prefill", "a-load-slow-to-arrive"])
def test_the_longest_turn_names_the_phase_that_held_it(engine, phase):
    eng = Engine(**engine)
    groups = run_rows(eng, f"turns-{phase}")
    longest = max(groups, key=lambda g: g["turn_ms_max"])
    assert longest["turn_max_phase"] == phase
    assert longest["turn_ms_max"] >= 30.0
    assert longest["turn_max_step"] in eng.steps
    assert sum(g["arrivals"] for g in groups) == 1


def test_a_slow_device_shows_as_the_loops_wait_and_nothing_else():
    groups = run_rows(Engine(launches=8, step_s=0.03), "turns-wait")
    wall = sum(g["wall_ms"] for g in groups)
    waited = sum(g["device_wait_ms"] for g in groups)
    # each turn but the first waits out the step before: 7 x 30 ms
    assert waited >= 7 * 30.0 * 0.9
    assert waited / wall > 0.8
    assert sum(sum(g["host_ms"].values()) for g in groups) < 0.2 * wall


def test_a_groups_phases_sum_to_its_wall_time_and_host_ms_keeps_its_keys():
    groups = run_rows(Engine(launches=40, step_s=0.001), "turns-sum", rows=3)
    assert len(groups) >= 2          # 40 steps: one full group and the rest
    for g in groups:
        assert set(g["host_ms"]) == set(tracing.AR_HOST_PHASES) == {
            "launch", "admit", "retire"}
        parts = sum(g["host_ms"].values()) + g["device_wait_ms"] \
            + g["record_ms"] + g["other_ms"]
        assert parts == pytest.approx(g["wall_ms"], abs=0.02)
        assert 0 < g["turn_ms_max"] <= g["wall_ms"] + 1e-3
        assert g["turn_max_phase"] in TURN_PHASES
        # nothing compiled on the loop's thread: the attributes are absent
        assert "compile_ms" not in g and "compiled" not in g


def test_the_series_gain_the_three_phases_and_the_turns_histogram():
    registry = MetricsRegistry()
    stats = tracing.step_stats()
    stats.bind_metrics(registry)
    before = dict(stats.host_s), stats.turns.snapshot().total
    eng = Engine(launches=5, step_s=0.02)
    run_rows(eng, "turns-series")
    assert stats.host_s["device_wait"] - before[0]["device_wait"] >= 0.06
    assert stats.turns.snapshot().total - before[1] == len(eng.steps)
    page = parse_prometheus_text(registry.render())
    phases = {labels["phase"] for labels, _ in
              page["sonata_ar_host_seconds_total"]}
    assert phases == set(TURN_PHASES)
    buckets = {labels["le"] for labels, _ in
               page["sonata_ar_turn_seconds_bucket"]}
    assert {"0.1", "+Inf"} <= buckets
    assert page["sonata_ar_turn_seconds_count"][0][1] >= len(eng.steps)


def test_outside_a_capture_the_loop_makes_no_annotation(monkeypatch):
    made = []
    real = profiling.annotation

    def watched(name, **ids):
        out = real(name, **ids)
        made.append((name, out))
        return out

    monkeypatch.setattr(steploop.profiling, "annotation", watched)
    run_rows(Engine(launches=3), "turns-quiet")
    assert made and all(out is profiling.NO_ANNOTATION for _, out in made)


def test_inside_a_capture_the_loop_mirrors_its_phases(monkeypatch):
    import jax

    seen, lock = [], threading.Lock()

    @contextlib.contextmanager
    def fake(name, **ids):
        with lock:
            seen.append((name, ids))
        yield

    monkeypatch.setattr(jax.profiler, "TraceAnnotation", fake)
    monkeypatch.setattr(profiling, "_capturing", True)
    eng = Engine(launches=3)
    run_rows(eng, "turns-capture")
    names = {name for name, _ in seen}
    assert names == {"sonata:admit", "sonata:launch", "sonata:retire",
                     "sonata:settle"}
    assert {ids["step_no"] for name, ids in seen
            if name == "sonata:launch"} == set(eng.steps)
    # one admit for the one turn that had an arrival
    assert sum(name == "sonata:admit" for name, _ in seen) == 1


def test_a_launch_that_compiled_after_the_warm_up_counts_against_the_voice():
    sc = Scope(slos="error_rate:0.01")
    scope_mod.install(sc)
    try:
        cold = {"compile": "cold", "compile_ms": 12.5,
                "compiled": ["lfm2_prefill"]}
        run_rows(Engine(launches=2, prefill_compile=cold), "unit-before")
        assert sc.runtime_cold_compiles("unit-before") == 0   # still warming
        sc.mark_warmup_complete()
        run_rows(Engine(launches=2, prefill_compile=cold), "unit-after")
        assert sc.runtime_cold_compiles("unit-after") == 1
        run_rows(Engine(launches=2), "unit-cached")
        assert sc.runtime_cold_compiles("unit-cached") == 0
        # the stock path's tables are as they were: no dispatch was noted
        assert sc.dispatches_total == 0 and sc.cold_compiles_total == 0
        assert sc.buckets_snapshot()["buckets"] == []
    finally:
        scope_mod.uninstall(sc)
        tracing.compile_stats().stage = "warmup"


def test_the_report_reads_the_four_names_of_a_capture():
    from tools import profile_report

    def note(name, start_ms, dur_ms, **ids):
        return {"name": name, "start_ns": start_ms * 1e6,
                "dur_ns": dur_ms * 1e6,
                "ids": {k: str(v) for k, v in ids.items()}}

    notes = [note("sonata:launch", 0.0, 1.0, step_no=7),
             note("sonata:retire", 1.0, 0.5, step_no=7),
             note("sonata:settle", 1.5, 13.0, step_no=7),
             note("sonata:admit", 15.0, 40.0, step_no=8),
             note("sonata:launch", 55.0, 1.5, step_no=8),
             note("sonata:retire", 56.5, 0.5, step_no=8),
             note("sonata:settle", 57.0, 60.0, step_no=8),
             note("sonata:enqueue", 3.0, 2.0, dispatch_id="a1")]
    got = profile_report.loop_turns(notes)
    assert set(got["phases"]) == {"admit", "launch", "retire", "settle"}
    assert got["phases"]["settle"] == {"count": 2, "total_ms": 73.0,
                                       "max_ms": 60.0, "max_step_no": 8}
    assert got["turns"] == 2
    assert got["longest_turn"] == {"step_no": 8, "ms": 102.0}
    assert profile_report.loop_turns(notes[-1:]) is None   # the stock path


# -- what the slots' cache is: the engine's description, and no word of the
# loop's ---------------------------------------------------------------------

def test_the_loop_carries_what_a_description_brings_and_knows_none_of_it():
    """A row sum, a static attribute, something a closed group derives and a
    resident series the loop has never heard of: a step group's span and
    ``StepStats`` carry them under the description's own names."""
    engine = Engine(launches=5)
    engine.description = types.SimpleNamespace(
        static={"crystal_layers": 7},
        row_sums=("facets_polished", "facets_seen"),
        rows=[(3 * n, n) for n in range(64)],
        closed=lambda group: {"facet_bytes": 16 * group["facets_seen"]},
        resident={"sonata_crystal_resident_bytes": 4096},
        prefill=lambda text_bucket: {})
    stats = tracing.step_stats()
    before = stats.resident.get("sonata_crystal_resident_bytes", 0)
    known = dict(stats.resident)
    loop = steploop.StepLoop(engine, name="crystal")
    try:
        assert stats.resident["sonata_crystal_resident_bytes"] \
            == before + 4096
    finally:
        loop.close()
    assert stats.resident["sonata_crystal_resident_bytes"] == before
    assert {k: stats.resident[k] for k in known} == known
    groups = run_rows(engine, "crystal", rows=2)
    # a row's launch number ``d`` attends over ``d + 1`` (``Plan``)
    seen = 2 * sum(d + 1 for d in range(5))
    assert sum(g["kv_positions"] for g in groups) == seen
    assert sum(g["facets_seen"] for g in groups) == seen
    assert sum(g["facets_polished"] for g in groups) == 3 * seen
    for g in groups:
        assert g["crystal_layers"] == 7
        assert g["facet_bytes"] == 16 * g["facets_seen"]
        # and nothing a real backbone's description would have brought
        assert not {"ssm_layers", "latent_layers", "kv_places_fetched",
                    "window", "mla_form"} & set(g)


def test_the_loops_source_names_no_kind_of_cache():
    """No branch on a kind of cache: the words stand in the module only
    where they are another thing's own name (the stock voice's window
    decodes, ``brings``)."""
    source = Path(steploop.__file__).read_text()
    assert "WINDOW_SUMS" not in source
    assert source.count("getattr(") == 1        # ``carries``, the one left
    for word in ("latent", "ssm", "ring_", "mla", "kv_places",
                 "kv_cache_bytes", "window_"):
        assert word not in source, word
    assert source.count("window") == 1          # "window decodes", line 4


# -- admitting rows: in a step, or apart ---------------------------------------

class Held(Engine):
    """An engine that offers no step that carries an arrival, and records
    what it is asked: ``seen`` every launch (its step number, the slots
    live in it, the slot whose prompt it carried), ``prefilled`` every
    prefill (the launches before it, its slot).  Its first program (a
    prefill) waits for ``gate`` and sets ``entered`` first, so that a test
    can queue rows behind it."""

    def __init__(self, launches=6):
        super().__init__(launches=launches)
        self.seen, self.prefilled = [], []
        self.entered, self.gate = threading.Event(), threading.Event()

    def _hold(self):
        if not self.entered.is_set():
            self.entered.set()
            assert self.gate.wait(30.0)

    def prefill(self, cache, slot, ids, temperature):
        self._hold()
        self.prefilled.append((len(self.seen), slot))
        return super().prefill(cache, slot, ids, temperature)

    def step(self, cache, live, temperature, step_no, carried=None):
        self._hold()
        self.seen.append((step_no, set(np.flatnonzero(live)), carried))
        return super().step(cache, live, temperature, step_no)


class Carrying(Held):
    """An engine whose step carries an arrival, but for the prompt lengths
    of ``apart``."""

    def __init__(self, launches=6, apart=()):
        super().__init__(launches=launches)
        self.apart = set(apart)

    def carries(self, n_ids: int) -> bool:
        return n_ids not in self.apart

    def step_admit(self, cache, live, temperature, step_no, slot, ids,
                   row_temperature):
        cache, kept, load = self.step(cache, live, temperature, step_no,
                                      carried=slot)
        return cache, kept, load, None, {
            "compile": "cached", "expert_matmul": "ragged_dot",
            "attention": "einsum", "text_bucket": 32}


def rows_behind_the_first(engine, name: str, prompts: list):
    """One row starts the loop, the others queue while the engine holds
    its first program, then all run out.  Returns the step groups and each
    row's ``prefill`` span, in the order submitted."""
    tracer = tracing.default_tracer()
    loop = steploop.StepLoop(engine, name=name)
    futures = []
    try:
        for k, ids in enumerate(prompts):
            with tracer.trace_request("test", request_id=f"{name}-{k}"):
                futures.append(loop.submit(ids, 8, 0.0))
            if k == 0:
                assert engine.entered.wait(30.0)
        engine.gate.set()
        for f in futures:
            assert f.result(timeout=60.0).shape == (8,)
        deadline = time.monotonic() + 30.0
        while True:     # the loop hands its last group on a turn later
            traces = {t.request_id: t for t in tracer.recent_traces()}
            groups = [s.attrs for rid, t in traces.items()
                      if rid.startswith(f"ar-steps-{name}-")
                      for s in t.spans_snapshot()
                      if s.attrs.get("kind") == "step"]
            if sum(g["steps"] for g in groups) == len(engine.seen):
                break
            assert time.monotonic() < deadline
            time.sleep(0.01)
    finally:
        loop.close()
    spans = []
    for k in range(len(prompts)):
        (span,) = [s.attrs for s in traces[f"{name}-{k}"].spans_snapshot()
                   if s.attrs.get("kind") == "prefill"]
        spans.append(span)
    return groups, spans


def admits(page: dict, voice: str) -> dict:
    return {labels["how"]: value
            for labels, value in page.get("sonata_ar_admits_total", [])
            if labels["voice"] == voice}


@pytest.mark.parametrize("rows", [1, 4])
def test_rows_waiting_together_take_a_launch_each_one_prompt_a_launch(rows):
    registry = MetricsRegistry()
    stats = tracing.step_stats()
    stats.bind_metrics(registry)
    eng = Carrying(launches=5)
    name = f"carry-{rows}"
    prompts = [[1] * (3 + k) for k in range(rows)]
    groups, spans = rows_behind_the_first(eng, name, prompts)
    assert not eng.prefilled                    # no prefill program ran
    carrying = [(step_no, slot) for step_no, _, slot in eng.seen
                if slot is not None]
    # k rows: k launches back to back from the loop's first, a prompt each
    assert [step_no for step_no, _ in carrying] == list(range(rows))
    assert sorted(slot for _, slot in carrying) == list(range(rows))
    for step_no, slot in carrying:
        lived = [n for n, live, _ in eng.seen if slot in live]
        # not live in the launch that carried it, live from the next one on,
        # for as many launches as its plan said
        assert lived == list(range(step_no + 1, step_no + 1 + 5))
    # the live rows advanced while the later arrivals' prompts ran
    assert eng.seen[rows - 1][1] == set(range(rows - 1))
    assert [(s["admit"], s["step_no"], s["tokens"]) for s in spans] == [
        ("step", k, 3 + k) for k in range(rows)]
    assert all(s["wait_ms"] >= 0.0 and s["text_bucket"] == 32 for s in spans)
    assert sum(g["admit_steps"] for g in groups) == rows == sum(
        g["arrivals"] for g in groups)
    assert sum(g["prompt_tokens"] for g in groups) == sum(
        len(p) for p in prompts)
    # a slot holds a row in the launch that carries it and in its steps
    assert sum(g["live_slot_steps"] for g in groups) == rows * (1 + 5)
    assert sum(g["units"] for g in groups) == rows * 5
    assert admits(parse_prometheus_text(registry.render()), name) == {
        "step": float(rows)}


def test_an_engine_without_such_a_step_admits_as_before():
    registry = MetricsRegistry()
    stats = tracing.step_stats()
    stats.bind_metrics(registry)
    eng = Held(launches=5)
    groups, spans = rows_behind_the_first(eng, "apart", [[1, 2, 3]] * 4)
    # the row that woke the loop is prefilled and steps alone once; the
    # three that came meanwhile are prefilled in one turn, in front of one
    # step, and are live in it
    assert eng.prefilled == [(0, 0), (1, 1), (1, 2), (1, 3)]
    assert [live for _, live, _ in eng.seen[:2]] == [{0}, {0, 1, 2, 3}]
    assert all(carried is None for _, _, carried in eng.seen)
    assert [s["admit"] for s in spans] == ["apart"] * 4
    assert not any("step_no" in s for s in spans)
    assert sum(g["admit_steps"] + g["prompt_tokens"] for g in groups) == 0
    assert sum(g["arrivals"] for g in groups) == 4
    assert sum(g["live_slot_steps"] for g in groups) == 4 * 5
    assert admits(parse_prometheus_text(registry.render()), "apart") == {
        "apart": 4.0}


def test_a_prompt_the_step_does_not_take_is_prefilled_apart_beside_it():
    """Whether a row rides is asked per row: one whose prompt the engine's
    step does not take is prefilled apart in the same turn that carries
    the next one."""
    eng = Carrying(launches=5, apart={7})
    groups, spans = rows_behind_the_first(
        eng, "mixed", [[1] * 3, [1] * 7, [1] * 4, [1] * 5])
    assert [s["admit"] for s in spans] == ["step", "apart", "step", "step"]
    # the second turn prefills the row of 7 and carries the row of 4; the
    # row of 5 waits for the launch after
    assert eng.prefilled == [(1, 1)]
    assert [(n, carried) for n, _, carried in eng.seen[:3]] == [
        (0, 0), (1, 2), (2, 3)]
    assert eng.seen[1][1] == {0, 1} and eng.seen[2][1] == {0, 1, 2}
    assert sum(g["admit_steps"] for g in groups) == 3
    assert sum(g["arrivals"] for g in groups) == 4


# -- the set of a span's attributes, a backbone ---------------------------------

#: what every unit voice's ``kind: step`` span carried at PR 46 (less
#: ``compile_ms`` and ``compiled``, which hang on the compile cache)
STEP_ATTRS = (
    "admit_steps", "arrivals", "assignments", "attention", "block_length",
    "commit_row_passes", "denoise_row_passes", "denoising_steps",
    "device_wait_ms", "expert_matmul", "experts_touched", "held_assignments",
    "held_experts_touched", "held_overflow_steps", "host_ms", "kind",
    "kv_places_fetched", "kv_positions", "latent_cache_bytes",
    "latent_layers", "latent_places_fetched", "layers", "live_slot_steps",
    "max_expert_assignments", "other_ms", "positions", "prompt_tokens",
    "record_ms", "slots", "ssm_layers", "ssm_state_bytes", "steps",
    "turn_max_phase", "turn_max_step", "turn_ms_max", "units", "wall_ms")
#: and every ``kind: prefill`` span
PREFILL_ATTRS = (
    "admit", "attention", "blocks", "compile", "expert_matmul", "kind",
    "rows", "slot", "tail_ids", "text_bucket", "tokens", "wait_ms")


@pytest.mark.parametrize("tiny, step_more, prefill_more", [
    ("lfm2", (), ("step_no",)),
    ("sdar", (), ()),
    ("nemotron", (), ("ssm_chunks", "step_no")),
    ("pangu", ("mla_form",), ("mla_form", "step_no")),
    ("laguna", ("full_layers", "kv_cache_bytes", "window",
                "window_bound_row_steps", "window_layers"),
     ("step_no", "window_layers")),
    ("gigachat", ("delta_layers", "delta_state_bytes", "mla_form"),
     ("delta_chunks", "mla_form", "step_no")),
])
def test_a_voices_spans_carry_the_attributes_they_carried(
        tiny, step_more, prefill_more, tmp_path, monkeypatch):
    """The *set* of names on a unit voice's step groups and prefill spans,
    by backbone, as read at PR 46: what a reader tells cells apart by
    (``"ssm_state_bytes" in g``, ``g.get("window_layers")``) is present
    where it was and absent where it was."""
    import importlib
    import json

    from sonata_tpu.models import from_config_path
    from sonata_tpu.models.config import SynthesisConfig

    gen = importlib.import_module(f"perfbench.harness.{tiny}gen")
    config = json.loads((Path(__file__).parent / "perfbench/data"
                         / f"{tiny}-tiny.json").read_text())
    monkeypatch.setenv("SONATA_AR_SLOTS", "3")
    monkeypatch.setenv("SONATA_AR_POSITIONS", "256")
    voice = from_config_path(gen.write_tensors(tmp_path, config))
    voice.set_fallback_synthesis_config(SynthesisConfig(noise_scale=0.0))
    tracer = tracing.default_tracer()
    tracer.clear()
    try:
        with tracer.trace_request("test", request_id="row-0"):
            voice.speak_batch(list(voice.phonemize_text("one short row.")))
    finally:
        voice.close()
    traces = {t.request_id: t for t in tracer.recent_traces()}
    cache_state = {"compile_ms", "compiled"}
    (prefill,) = [s.attrs for s in traces["row-0"].spans_snapshot()
                  if s.attrs.get("kind") == "prefill"]
    assert sorted(set(prefill) - cache_state) == sorted(
        PREFILL_ATTRS + prefill_more)
    groups = [s.attrs for rid, t in traces.items()
              if rid.startswith("ar-steps-") for s in t.spans_snapshot()
              if s.name == "dispatch"]
    assert groups
    for g in groups:
        assert sorted(set(g) - cache_state) == sorted(STEP_ATTRS + step_more)
