"""The shape decision of a stock voice (``sonata_tpu/models/shape_plan.py``)
held to what the parent commit gave.

``shape_plan_pins.json`` was captured at d97beed (PR 29) from
``PiperVoice``'s own ``lattice_shapes``, ``_plan_dispatch_groups``,
``_frame_budget``, ``_estimate_frame_bucket`` and ``_observe_frames``,
before PR 30 moved them: PR 30 moves the decision and must not change it.
A later PR that means to change a plan (ROADMAP B2, B3, D10) changes the
pin with it, and says so.
"""

import json
from pathlib import Path

import pytest

from sonata_tpu.models import shape_plan
from sonata_tpu.models.shape_plan import FrameEstimator
from sonata_tpu.utils.buckets import FRAME_BUCKETS, bucket_for
from sonata_tpu.utils.dispatch_policy import (
    _coalescing_policy,
    _per_request_policy,
)
from voices import tiny_multispeaker_voice, tiny_voice

PINS = json.loads(
    (Path(__file__).parent / "shape_plan_pins.json").read_text())


# ---------------------------------------------------------------------------
# the estimator
# ---------------------------------------------------------------------------

def test_the_four_constants_are_named_once():
    assert (shape_plan.FRAMES_PER_ID_PRIOR, shape_plan.BUDGET_HEADROOM,
            shape_plan.FIRST_OBSERVATION_MARGIN,
            shape_plan.DECAY) == (2.5, 1.08, 1.15, 0.995)


def test_estimator_starts_at_the_prior_unobserved():
    est = FrameEstimator()
    assert (est.frames_per_id, est.observed) == (2.5, False)


def test_first_observation_replaces_the_prior_with_a_margin():
    est = FrameEstimator()
    est.observe(100.0, 180)
    assert est.observed and est.frames_per_id == (180 / 100.0) * 1.15


def test_a_lower_observation_decays_by_half_a_percent_a_dispatch():
    est = FrameEstimator()
    est.observe(100.0, 180)
    first = est.frames_per_id
    est.observe(100.0, 100)
    est.observe(100.0, 100)
    assert est.frames_per_id == first * 0.995 * 0.995


def test_a_higher_observation_jumps_up_at_once():
    est = FrameEstimator()
    est.observe(100.0, 180)
    est.observe(50.0, 200)
    assert est.frames_per_id == 4.0


def test_an_observation_under_one_weighted_id_divides_by_one():
    est = FrameEstimator()
    est.observe(0.5, 3)
    assert est.frames_per_id == 3 * 1.15


@pytest.mark.parametrize("weighted", [1.0, 50.0, 137.5, 1000.0])
def test_budget_is_the_estimate_with_headroom_and_bucket_rounds_it_up(
        weighted):
    est = FrameEstimator()
    est.observe(100.0, 180)
    budget, fpi = est.budget(weighted)
    assert fpi == est.frames_per_id
    assert budget == max(int(weighted * fpi * 1.08), 1)
    assert est.bucket(weighted) == bucket_for(budget, FRAME_BUCKETS)


@pytest.mark.parametrize("name", sorted(PINS["estimator"]))
def test_estimator_after_a_sequence_is_the_parents(name):
    pin = PINS["estimator"][name]
    est = FrameEstimator()
    states = [[est.frames_per_id, est.observed]]
    for weighted, frames in pin["observations"]:
        est.observe(weighted, frames)
        states.append([est.frames_per_id, est.observed])
    assert states == pin["states"]
    assert [list(est.budget(w)) for w in pin["budget_of"]] == pin["budgets"]
    assert [est.bucket(w) for w in pin["bucket_of"]] == pin["buckets"]


# ---------------------------------------------------------------------------
# the planner
# ---------------------------------------------------------------------------

def plan(lengths, scales=None):
    scales = [1.0 if s is None else s
              for s in (scales or [None] * len(lengths))]
    return shape_plan.plan_dispatch_groups(lengths, scales, min_batch=8,
                                           max_batch=64)


def test_eight_rows_are_one_sorted_group_as_the_cell_sends():
    lengths = [120, 95, 150, 101, 88, 143, 131, 110]
    (group,) = plan(lengths)
    assert [lengths[i] for i in group] == sorted(lengths)


def test_thirty_two_rows_are_two_groups_of_sixteen():
    groups = plan([60 + (i * 37) % 90 for i in range(32)])
    assert [len(g) for g in groups] == [16, 16]


def test_a_leftover_under_eight_rides_in_its_neighbour():
    groups = plan([40 + (i * 29) % 100 for i in range(19)])
    assert [len(g) for g in groups] == [11, 8]  # 3 + 8, then 8


def test_a_text_bucket_jump_past_twice_splits_the_group():
    assert plan([10, 12, 100, 14, 400, 90]) == [[0, 1, 3], [5, 2], [4]]


@pytest.mark.parametrize("name", sorted(PINS["planner"]))
def test_dispatch_groups_are_the_parents(name):
    pin = PINS["planner"][name]
    groups = plan(pin["lengths"], pin["scales"])
    assert groups == pin["groups"]
    assert sorted(i for g in groups for i in g) == list(
        range(len(pin["lengths"])))


def test_the_voice_plans_with_its_own_batch_limits_and_row_scales(
        monkeypatch):
    """``speak_batch`` hands the planner each row's own length scale,
    the config's where a row has none."""
    voice = tiny_voice()
    sc = voice.get_fallback_synthesis_config()
    slow = sc.copy()
    slow.length_scale = 0.04
    seen = {}
    real = shape_plan.plan_dispatch_groups

    def spy(lengths, length_scales, **limits):
        seen.update(lengths=list(lengths), scales=list(length_scales),
                    **limits)
        return real(lengths, length_scales, **limits)

    monkeypatch.setattr(shape_plan, "plan_dispatch_groups", spy)
    try:
        voice.speak_batch(["həlˈoʊ", "wˈɜːld"], scales=[slow, None])
    finally:
        voice.close()
    assert seen["scales"] == [0.04, sc.length_scale]
    assert (seen["min_batch"], seen["max_batch"]) == (8, 64)
    assert len(seen["lengths"]) == 2


# ---------------------------------------------------------------------------
# the lattice
# ---------------------------------------------------------------------------

def lattice_voice(setup, monkeypatch):
    for knob in ("SONATA_BATCH_MODE", "SONATA_DISPATCH_POLICY"):
        monkeypatch.delenv(knob, raising=False)
    if setup["batch_mode_env"]:
        monkeypatch.setenv("SONATA_BATCH_MODE", setup["batch_mode_env"])
    voice = tiny_voice()
    make = (_coalescing_policy if setup["policy"] == "coalescing"
            else _per_request_policy)
    voice._dispatch_policy = make("cpu", "test")
    if setup["observe"]:
        voice.frame_estimator.observe(*setup["observe"])
    if setup["length_scale"]:
        sc = voice.get_fallback_synthesis_config()
        sc.length_scale = setup["length_scale"]
        voice.set_fallback_synthesis_config(sc)
    return voice


@pytest.mark.parametrize("mode", ["off", "minimal", "full"])
@pytest.mark.parametrize("name", sorted(PINS["lattice"]))
def test_lattice_of_a_voice_is_the_parents(name, mode, monkeypatch):
    pin = PINS["lattice"][name]
    voice = lattice_voice(pin["setup"], monkeypatch)
    try:
        assert [list(s) for s in voice.lattice_shapes(mode)] == pin[mode]
    finally:
        voice.close()


def test_a_multi_speaker_voice_warms_window_decoders_that_take_a_speaker(
        monkeypatch):
    monkeypatch.delenv("SONATA_BATCH_MODE", raising=False)
    voice = tiny_multispeaker_voice()
    voice._dispatch_policy = _coalescing_policy("cpu", "test")
    try:
        wdec = [s for s in voice.lattice_shapes("minimal")
                if s[0] == "wdec"]
        assert wdec[:3] == [("wdec", 64, 1, True), ("wdec", 128, 1, True),
                            ("wdec", 256, 1, True)]
    finally:
        voice.close()


def test_off_resolves_no_policy():
    voice = tiny_voice()
    try:
        assert voice.lattice_shapes("off") == []
        assert voice._dispatch_policy is None  # no probe was paid
    finally:
        voice.close()


def test_a_typo_in_the_batch_mode_keeps_the_full_pipeline_shapes(
        monkeypatch):
    """``SONATA_BATCH_MODE`` fails loudly at stream time; a boot's lattice
    is then the triples alone."""
    monkeypatch.setenv("SONATA_BATCH_MODE", "iterashun")
    voice = tiny_voice()
    voice._dispatch_policy = _coalescing_policy("cpu", "test")
    try:
        full = voice.lattice_shapes("full")
    finally:
        voice.close()
    assert [list(s) for s in full] == [
        s for s in PINS["lattice"]["coalescing_prior"]["full"]
        if s[0] != "wdec"]


@pytest.mark.parametrize("f, want", [
    (64, {128}), (768, {512, 1024}), (4096, {3072}), (8192, set()),
    (100, set())])
def test_neighbour_frame_buckets(f, want):
    assert shape_plan.neighbor_frame_buckets(f) == want


@pytest.mark.parametrize("mode, max_batch, want", [
    ("iteration", 8, [1, 2, 4, 8]), ("iteration", 1, [1]),
    ("dispatch", 8, [1, 8]), ("dispatch", 1, [1])])
def test_window_decoder_batches(mode, max_batch, want):
    assert shape_plan.window_decoder_batches(mode, max_batch) == want
