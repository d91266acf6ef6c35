"""The unit-LM cell's files on the CPU: the tiny configuration of the same
family end to end through the same writer, server command, reference,
comparison, limits and readers as ``lfm2-24b-a2b``; the comparison's
controls; every new reader on a recorded run; the cost file against a hand
count; the traffic file against the list it was cut from."""

import json
import shutil
from pathlib import Path

import numpy as np
import pytest

from perfbench import run
from perfbench.harness import lfm2_costs, lfm2gen, parts

ROOT = Path(__file__).resolve().parent.parent.parent
DATA = Path(__file__).resolve().parent / "data"
BENCH = DATA / "lfm2-tiny-benchmark.json"
TINY = json.loads((DATA / "lfm2-tiny.json").read_text())
REAL = json.loads((ROOT / "perfbench/configs/lfm2/lfm2-24b-a2b.json").read_text())
CELL = "lfm2-24b-a2b.batch.sentence"
SEED = 3000000007


@pytest.fixture(scope="module")
def tiny_run(tmp_path_factory):
    """One traced run of the tiny cell; what the timed path left for the
    comparison is kept, so that the controls need no second server."""
    kept = tmp_path_factory.mktemp("lfm2_kept")

    def keep(done):
        work = Path(done["sampled_audio"]).parent
        shutil.copytree(work / "ar_dump", kept / "ar_dump")
        shutil.copy(done["sampled_audio"], kept / "sampled_audio.npz")
        (kept / "done.json").write_text(json.dumps(done["sampled"]))

    out = run.run_cell("lfm2-tiny.sentence", SEED, 2.0, True,
                       benchmark_file=BENCH, platform="cpu",
                       require_accelerator=False, alter_audio=keep)
    return out, kept


def job_of(kept: Path) -> dict:
    return {"root": str(ROOT), "paths": ["perfbench", "tests/perfbench"],
            "config_file": "tests/perfbench/data/lfm2-tiny.json",
            "seed": SEED, "words": "perfbench/traffic/words.tsv",
            "sampled": json.loads((kept / "done.json").read_text()),
            "sampled_audio": str(kept / "sampled_audio.npz"),
            "work_dir": str(kept)}


def judged(numbers: dict) -> bool:
    limits = parts.load_limits(ROOT, ["perfbench", "tests/perfbench"],
                               "lfm2-tiny")
    return all(numbers.get(k) is not None and numbers[k] <= v
               for k, v in limits.items())


def test_the_tiny_cell_is_correct_end_to_end(tiny_run):
    out, kept = tiny_run
    assert out["correct"] is True and out["failed"] == 0 < out["attempted"]
    assert set(out["compared"]) == {
        "audio_err_max", "rows_length_off", "logit_err_median",
        "logit_err_p99", "route_flip_share", "logit_err_forced_median",
        "logit_err_forced_p99", "route_flip_forced_share",
        "greedy_regret_max"}
    assert out["compared"]["rows_length_off"]["value"] == 0
    # span metrics are read on the CPU too; device metrics are left out
    assert set(out["metrics"]) == {
        "ar.rows_per_step.sentence", "ar.empty_slot_share.sentence",
        "ar.host_ms_per_step.sentence",
        "ar.vocode_padding_share.sentence", "ar.finish_ms_per_row.sentence",
        "moe.experts_touched_per_step.sentence",
        "moe.max_expert_load_share.sentence",
        "text.phonemize_ms_per_req.sentence",
        "warmup.cold_compiles_in_window.sentence"}
    # six callers over four slots: every step is full
    assert out["metrics"]["ar.rows_per_step.sentence"]["value"] == 4.0
    assert 2.0 <= out["metrics"]["moe.experts_touched_per_step.sentence"][
        "value"] <= 8.0
    info = out["info"]
    assert info["rows"] == info["rows_compared"] == 6
    assert info["steps_compared"] == info["frames_compared"] > 500
    assert len(list((kept / "ar_dump").glob("pb-check-*.npz"))) == 6
    json.dumps(out)


def test_the_kept_dump_is_judged_as_the_run_was(tiny_run, monkeypatch):
    out, kept = tiny_run
    monkeypatch.setenv("PERFBENCH_ALSO_CONTROLS", "reference_fp8,wrong_unit")
    check = parts.load_file(ROOT / "perfbench/reference/lfm2_check.py")
    compared = check.compare(job_of(kept), TINY)
    numbers = compared["numbers"]
    assert judged(numbers) and compared["info"]["numbers"] == numbers
    # the controls' numbers beside the sound run's, the run left as it is
    low = compared["info"]["controls"]["reference_fp8"]
    assert not judged(low) and set(low) >= set(numbers) - {
        "rows_length_off"}
    assert low["audio_err_max"] > 10 * numbers["audio_err_max"]
    # on the served routes no near-tie stands between the two: what is
    # left is the products' bfloat16 inputs, the tail of the logit error
    # and of the regret collapse, and fewer decisions differ
    info = compared["info"]
    assert numbers["logit_err_forced_p99"] < 0.2 * numbers["logit_err_p99"]
    assert numbers["greedy_regret_max"] < 0.2 * info["greedy_regret_own_max"]
    assert numbers["route_flip_forced_share"] < numbers["route_flip_share"]
    # one wrong unit in one row moves the regret and nothing else
    wrong = compared["info"]["controls"]["wrong_unit"]
    assert wrong["greedy_regret_max"] > 0.5 and not judged(
        dict(wrong, rows_length_off=0))
    assert {k: v for k, v in wrong.items() if k not in (
        "greedy_regret_max", "greedy_regret_p99", "greedy_regret_own_max",
        "greedy_regret_own_p99")} == {
        k: v for k, v in dict(numbers, **compared["info"]).items()
        if k in wrong and not k.startswith("greedy_regret")}
    for name, c in out["compared"].items():
        assert numbers[name] == pytest.approx(c["value"], rel=1e-3, abs=1e-6)


def test_the_precision_below_is_not_correct(tiny_run, monkeypatch):
    """The control: float8 weights and bfloat16 where float32 is stated."""
    monkeypatch.setenv("PERFBENCH_CONTROL", "reference_fp8")
    check = parts.load_file(ROOT / "perfbench/reference/lfm2_check.py")
    out = check.compare(job_of(tiny_run[1]), TINY)
    assert not judged(out["numbers"])
    assert out["numbers"]["logit_err_median"] > 0.3
    assert out["numbers"]["route_flip_share"] > 0.1
    assert out["numbers"]["logit_err_forced_median"] > 0.1
    assert out["info"]["control"] == "reference_fp8"


def test_a_wrong_unit_is_not_correct(tiny_run, monkeypatch):
    """The control ``greedy_regret_max`` is there for: one step of one row
    read as another unit than the served path's best."""
    monkeypatch.setenv("PERFBENCH_CONTROL", "wrong_unit")
    check = parts.load_file(ROOT / "perfbench/reference/lfm2_check.py")
    out = check.compare(job_of(tiny_run[1]), TINY)
    limits = parts.load_limits(ROOT, ["perfbench", "tests/perfbench"],
                               "lfm2-tiny")
    over = [k for k, v in limits.items() if out["numbers"][k] > v]
    assert over == ["greedy_regret_max"]
    assert out["info"]["control"] == "wrong_unit"


@pytest.mark.parametrize("what", ["logit", "unit", "frame"])
def test_an_altered_dump_is_not_correct(tiny_run, tmp_path, what):
    kept = tmp_path / "kept"
    shutil.copytree(tiny_run[1], kept)
    path = sorted((kept / "ar_dump").glob("*.npz"))[0]
    with np.load(path) as f:
        dump = {k: f[k] for k in f.files}
    if what == "logit":
        dump["logits"][1, 300] += 100.0
    elif what == "unit":
        dump["units"][5] = 256 + (dump["units"][5] - 255) % 200
    else:
        dump["units"] = dump["units"][:-1]
    with open(path, "wb") as f:
        np.savez(f, **dump)
    check = parts.load_file(ROOT / "perfbench/reference/lfm2_check.py")
    numbers = check.compare(job_of(kept), TINY)["numbers"]
    assert not judged(numbers)
    if what == "frame":
        assert numbers["rows_length_off"] == 1


# -- readers on a recorded run ----------------------------------------------

def group(steps, live, touched, fullest, kv):
    return {"name": "dispatch", "start": 10.0, "end": 10.4, "attrs": {
        "kind": "step", "steps": steps, "slots": 64,
        "live_slot_steps": live, "kv_positions": kv, "layers": [2, 3],
        "assignments": [4 * live, 4 * live], "experts_touched": touched,
        "max_expert_assignments": fullest,
        "host_ms": {"launch": 16.0, "admit": 4.0, "retire": 12.0}}}


def recorded_run() -> dict:
    spans = [group(32, 2048, [1920, 1984], [320, 352], 2048 * 500),
             group(32, 1984, [1900, 1940], [330, 340], 1984 * 480),
             {"name": "dispatch", "start": 10.1, "end": 10.12,
              "attrs": {"kind": "prefill", "rows": 1}},
             {"name": "dispatch", "start": 10.3, "end": 10.31,
              "attrs": {"kind": "vocode", "rows": 1, "frames_needed": 420,
                        "frames_bucket": 512, "fetch_wait_ms": 6.0,
                        "finish_ms": 1.5}},
             {"name": "dispatch", "start": 10.4, "end": 10.41,
              "attrs": {"kind": "vocode", "rows": 1, "frames_needed": 238,
                        "frames_bucket": 256, "fetch_wait_ms": 5.0,
                        "finish_ms": 0.5}},
             {"name": "phonemize", "start": 9.0, "end": 9.002, "attrs": {}},
             {"name": "encode-ids", "start": 9.002, "end": 9.003,
              "attrs": {}}]
    spans[1].update(start=12.0, end=12.4)
    modules = [{"name": f"jit_lfm2_step({k})", "dur_ns": 15e6}
               for k in range(40)]
    modules += [{"name": "jit_lfm2_prefill(7)", "dur_ns": 14e6}] * 5
    modules += [{"name": "jit_unit_vocode(9)", "dur_ns": 9e6}] * 4
    return {"spans": spans, "dims": lfm2gen.describe(REAL)["dims"],
            "device": {"kind": "TPU v5 lite"},
            "metrics_before": {"sonata_runtime_cold_compiles_total": 1.0},
            "metrics_after": {"sonata_runtime_cold_compiles_total": 1.0},
            "cache_entries_added": 0,
            "profile": {"wall_start": 9.5},
            "trace": {"busy_s": 0.8, "window_s": 1.0, "wall_t0": 10.2,
                      "modules": modules}}


def wanted_step_roofline() -> float:
    cost = lfm2_costs.step_cost(lfm2gen.backbone(REAL), 64.0,
                                (1920 + 1984) / 32, 2048 * 500 / 32)
    seconds = max(cost["ops"] / 197e12, cost["bytes"] / 819e9)
    return 100.0 * seconds / 0.015


WANTED = {
    "ar.rows_per_step.sentence": 63.0,
    "ar.empty_slot_share.sentence": 100.0 * (1 - 4032 / 4096),
    "ar.host_ms_per_step.sentence": 1.0,
    "ar.vocode_padding_share.sentence": 100.0 * (1 - 658 / 768),
    "ar.finish_ms_per_row.sentence": 1.0,
    "ar.prefill_device_share.sentence": 100.0 * 0.07 / 0.8,
    "ar.vocode_device_share.sentence": 100.0 * 0.036 / 0.8,
    "ar.step_ms.sentence": 15.0,
    "moe.experts_touched_per_step.sentence": 7744 / 128,
    "moe.max_expert_load_share.sentence": 100.0 * 1342 / (8 * 4032),
    "device.step_roofline.sentence": None,
    "device.idle_share.sentence": 20.0,
    "text.phonemize_ms_per_req.sentence": 3.0,
    "warmup.cold_compiles_in_window.sentence": 0.0,
}


@pytest.mark.parametrize("name", sorted(WANTED))
def test_each_new_reader_on_a_recorded_run(name):
    read = parts.load_reader(ROOT, ["perfbench"], name)
    want = WANTED[name]
    if want is None:
        # only the group that ended inside the traced interval (10.2-11.2)
        # says what the traced steps did
        want = wanted_step_roofline()
        assert 0.0 < want < 100.0
    assert read(recorded_run()) == pytest.approx(want, rel=1e-9)
    # a program without the spans or the programs (the parent): nothing
    # to read, nothing raised
    bare = dict(recorded_run(), spans=[], trace={}, metrics_after={},
                metrics_before={})
    if not name.startswith("warmup."):
        assert read(bare) is None
    entry = [m for m in json.loads((ROOT / "BENCHMARK.json").read_text())[
        "per_layer"] if m["name"] == name]
    assert entry and entry[0]["workloads"] == [CELL] \
        and entry[0]["moves"] == "audio_s_per_s"


def test_the_costs_against_a_hand_count_at_the_tiny_sizes():
    bb = lfm2gen.backbone(TINY)
    z = lfm2_costs.sizes(bb)
    assert z["conv_op"] == 64 * 192 + 3 * 64 + 64 * 64 == 16576
    assert z["attn_op"] == 64 * (4 + 2 * 2) * 16 + 64 * 64 + 2 * 16 == 12320
    assert z["dense_ffn"] == 3 * 64 * 96 and z["expert"] == 3 * 64 * 32
    assert z["router"] == 64 * 8 + 8 and z["embed"] == 512 * 64
    assert (z["conv_layers"], z["attn_layers"], z["dense_layers"],
            z["expert_layers"]) == (5, 1, 2, 4)
    cost = lfm2_costs.step_cost(bb, live_rows=3, experts_touched=4 * 5,
                                kv_positions=100)
    fixed = (5 * 16576 + 12320 + 2 * 18432 + 4 * 520 + 6 * 128 + 32768
             + 64)
    assert cost["bytes"] == 2 * (fixed + 20 * 6144) + 2 * 2 * 1 * 32 * 100
    active = 5 * 16576 + 12320 + 2 * 18432 + 4 * (520 + 2 * 6144) + 32768
    assert cost["ops"] == 2.0 * active * 3 + 2.0 * 2 * 1 * 64 * 100
    # the published sizes, by the issue's own count
    z = lfm2_costs.sizes(lfm2gen.backbone(REAL))
    assert z["expert"] == 9437184 and z["dense_ffn"] == 72351744
    assert round(z["conv_op"] / 1e6, 2) == 16.78
    assert round(z["attn_op"] / 1e6, 2) == 10.49
    specs = lfm2gen.tensor_specs(REAL)
    held = sum(int(np.prod(s[1])) for s in specs if s[0] != "unit_table")
    assert round(held / 1e9, 2) == 5.27


def test_the_traffic_is_the_stated_slice_of_the_paragraph_list():
    sentence = json.loads((ROOT / "perfbench/traffic/batch.sentence.json")
                          .read_text())
    paragraph = json.loads((ROOT / "perfbench/traffic/batch.paragraph.json")
                           .read_text())
    assert sentence["paragraphs"] == [[s] for p in paragraph["paragraphs"]
                                      for s in p[:2]]
    flat = [p[0] for p in sentence["paragraphs"]]
    assert (len(flat), min(flat), max(flat)) == (128, 33, 90)
    assert sum(n >= 80 for n in flat) == 7
    assert round(sum(flat) / 128, 1) == 58.9
    assert (sentence["kind"], sentence["callers"], sentence["rpc"],
            sentence["synthesis_mode"], sentence["words"]) == (
        "closed_paragraphs", 64, "SynthesizeUtterance", "PARALLEL",
        paragraph["words"])
    assert sentence["warmup"]["min_requests"] == 128
    assert sentence["warmup"]["settle_dispatches"] == 40
    assert sentence["check"]["requests"] == sentence["check"]["rows"] == 64
    assert sentence["check"]["inference"] == {"noise_scale": 0.0,
                                              "noise_w": 0.0}


def test_the_configuration_keeps_every_published_number():
    published = {
        "hidden_size": 2048, "num_attention_heads": 32,
        "num_key_value_heads": 8, "conv_L_cache": 3, "conv_bias": False,
        "intermediate_size": 11776, "moe_intermediate_size": 1536,
        "num_experts": 64, "num_experts_per_tok": 4, "norm_topk_prob": True,
        "use_expert_bias": True, "routed_scaling_factor": 1,
        "num_dense_layers": 2, "norm_eps": 1e-05, "vocab_size": 65536,
        "max_position_embeddings": 128000, "model_type": "lfm2_moe",
        "rope_parameters": {"rope_theta": 1000000, "rope_type": "default"}}
    for key, value in published.items():
        assert REAL[key] == value, key
    period = ["conv", "conv", "full_attention", "conv"]
    assert REAL["published"]["num_hidden_layers"] == 40
    assert REAL["published"]["layer_types"][:4] == period
    assert REAL["num_hidden_layers"] == 10
    assert REAL["layer_types"] == REAL["published"]["layer_types"][:10]
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    entry = [c for c in bench["configs"] if c["name"] == "lfm2-24b-a2b"][0]
    assert entry["reduced"] == ["num_hidden_layers", "layer_types"]
    assert set(REAL["reduced"]) - {"why"} == set(entry["reduced"])
    cells = [w for w in bench["workloads"] if w["config"] == "lfm2-24b-a2b"]
    assert [(w["name"], w["traffic"], w["chips"]) for w in cells] == [
        (CELL, "batch.sentence", 1)]
    # the generator is lessac-high's, key for key
    lessac = json.loads((ROOT / "perfbench/configs/lessac-high.json")
                        .read_text())
    assert REAL["voice"]["model"] == lessac["voice"]["model"]
    assert REAL["weights"]["seed"] == lessac["weights"]["seed"]
    assert lfm2gen.describe(REAL)["samples_per_frame"] == 256
    assert lfm2gen.describe(REAL)["frame_budget_estimator"] is False
