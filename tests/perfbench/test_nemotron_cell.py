"""The hybrid cell's files on the CPU: the tiny configuration of the same
family end to end through the same writer, server command, reference,
comparison and readers as ``nemotron-3-nano-30b-a3b``; the comparison's five
controls; every new reader on a recorded run; the cost file against a hand
count and against the issue's arithmetic; the traffic file against the one
it was cut from; the configuration against the published row.  Entries of
``BENCHMARK.json`` are found by name: no position is pinned."""

import ast
import json
import shutil
from pathlib import Path

import numpy as np
import pytest

from perfbench import run
from perfbench.harness import nemotron_costs, nemotrongen, parts

ROOT = Path(__file__).resolve().parent.parent.parent
DATA = Path(__file__).resolve().parent / "data"
BENCH = DATA / "nemotron-tiny-benchmark.json"
TINY = json.loads((DATA / "nemotron-tiny.json").read_text())
REAL_FILE = ROOT / "perfbench/configs/nemotron/nemotron-3-nano-30b-a3b.json"
REAL = json.loads(REAL_FILE.read_text())
CONFIG = "nemotron-3-nano-30b-a3b"
CELL = "nemotron-3-nano-30b-a3b.batch.sentence256"
CHECK = ROOT / "perfbench/reference/nemotron_check.py"
PATHS = ["perfbench", "tests/perfbench"]
SEED = 3000000007
CONTROLS = ("reference_fp8", "wrong_unit", "state_bf16", "no_shared",
            "stale_state")


@pytest.fixture(scope="module")
def tiny_run(tmp_path_factory):
    """One traced run of the tiny cell; what the timed path left for the
    comparison is kept, so that the controls need no second server."""
    kept = tmp_path_factory.mktemp("nemotron_kept")

    def keep(done):
        work = Path(done["sampled_audio"]).parent
        shutil.copytree(work / "ar_dump", kept / "ar_dump")
        shutil.copy(done["sampled_audio"], kept / "sampled_audio.npz")
        (kept / "done.json").write_text(json.dumps(done["sampled"]))

    out = run.run_cell("nemotron-tiny.sentence", SEED, 2.0, True,
                       benchmark_file=BENCH, platform="cpu",
                       require_accelerator=False, alter_audio=keep)
    return out, kept


def job_of(kept: Path) -> dict:
    return {"root": str(ROOT), "paths": PATHS,
            "config_file": "tests/perfbench/data/nemotron-tiny.json",
            "seed": SEED, "words": "perfbench/traffic/words.tsv",
            "sampled": json.loads((kept / "done.json").read_text()),
            "sampled_audio": str(kept / "sampled_audio.npz"),
            "work_dir": str(kept)}


def over(numbers: dict) -> list:
    """The limits ``numbers`` pass."""
    limits = parts.load_limits(ROOT, PATHS, "nemotron-tiny")
    return sorted(k for k, v in limits.items()
                  if numbers.get(k) is None or numbers[k] > v)


def test_the_tiny_cell_is_correct_end_to_end(tiny_run):
    out, kept = tiny_run
    assert out["correct"] is True and out["failed"] == 0 < out["attempted"]
    assert set(out["compared"]) == {
        "audio_err_max", "rows_length_off", "logit_err_median",
        "logit_err_p99", "route_flip_share", "logit_err_forced_median",
        "logit_err_forced_p99", "route_flip_forced_share",
        "route_flip_forced_start_share", "state_err_p99",
        "greedy_regret_max"}
    assert out["compared"]["rows_length_off"]["value"] == 0
    # span metrics are read on the CPU too; device metrics are left out
    assert set(out["metrics"]) == {
        f"{name}.hybrid" for name in (
            "ar.rows_per_step", "ar.empty_slot_share", "ar.host_ms_per_step",
            "ar.vocode_padding_share", "ar.finish_ms_per_row",
            "ssm.state_share_of_step_bytes", "moe.experts_touched_per_step",
            "moe.rows_per_expert", "moe.held_assignment_share",
            "moe.max_expert_load_share", "moe.grouped_step_share",
            "text.phonemize_ms_per_req", "warmup.cold_compiles_in_window")}
    value = {k: v["value"] for k, v in out["metrics"].items()}
    # six callers over four slots: every step is full
    assert value["ar.rows_per_step.hybrid"] == 4.0
    # four of eight experts held: about half of the assignments, and never
    # more than four experts touched a layer
    assert 25.0 < value["moe.held_assignment_share.hybrid"] < 75.0
    assert 1.0 <= value["moe.experts_touched_per_step.hybrid"] <= 4.0
    assert value["moe.rows_per_expert.hybrid"] >= 1.0
    assert 0.0 < value["ssm.state_share_of_step_bytes.hybrid"] < 100.0
    assert value["moe.grouped_step_share.hybrid"] == 0.0    # the CPU
    info = out["info"]
    assert info["rows"] == info["rows_compared"] == 6
    assert info["steps_compared"] == info["frames_compared"] > 500
    dumps = sorted((kept / "ar_dump").glob("pb-check-*.npz"))
    assert len(dumps) == 6
    with np.load(dumps[0]) as f:
        assert f["state"].shape == (8, 8, 16) and f["state"].any()
    json.dumps(out)


def test_the_kept_dump_is_judged_as_the_run_was(tiny_run, monkeypatch):
    out, kept = tiny_run
    monkeypatch.setenv("PERFBENCH_ALSO_CONTROLS", ",".join(CONTROLS))
    compared = parts.load_file(CHECK).compare(job_of(kept), TINY)
    numbers, info = compared["numbers"], compared["info"]
    assert over(numbers) == [] and info["numbers"] == numbers
    for name, c in out["compared"].items():
        assert numbers[name] == pytest.approx(c["value"], rel=1e-3, abs=1e-6)
    controls = info["controls"]
    assert set(controls) == set(CONTROLS)
    for name, read in controls.items():
        assert over(dict(read, rows_length_off=0)), name
    # on the served routes no near-tie stands between the two
    assert numbers["logit_err_forced_p99"] < 0.5 * numbers["logit_err_p99"]
    assert numbers["route_flip_forced_share"] <= numbers["route_flip_share"]
    # the precision below: every number of the arithmetic, the audio too
    low = controls["reference_fp8"]
    assert low["logit_err_forced_median"] > 10 * numbers[
        "logit_err_forced_median"]
    assert low["state_err_p99"] > 10 * numbers["state_err_p99"]
    assert low["audio_err_max"] > 10 * numbers["audio_err_max"]
    # one wrong unit in one row moves the regret and nothing else
    wrong = controls["wrong_unit"]
    assert wrong["greedy_regret_max"] > 0.5
    assert {k: v for k, v in wrong.items()
            if not k.startswith("greedy_regret")} == {
        k: v for k, v in dict(numbers, **info).items()
        if k in wrong and not k.startswith("greedy_regret")}
    # without the shared expert nothing is near
    assert controls["no_shared"]["logit_err_forced_median"] > 0.3
    # a state left in the slot shows where a row starts and in what the
    # row leaves, far more than in the logits' median
    stale = controls["stale_state"]
    assert stale["route_flip_forced_start_share"] > 10 * max(
        numbers["route_flip_forced_start_share"], 0.005)
    assert stale["route_flip_forced_start_share"] > 2 * stale[
        "route_flip_forced_share"]
    assert stale["state_err_p99"] > 3 * numbers["state_err_p99"]
    # a state rounded to bfloat16 moves the logits and the state *less* than
    # the products' bfloat16 inputs move the program's: no number of the
    # arithmetic tells it from the stated float32 (PERF.md §7); its routes
    # are not the served ones, and that alone is what the regret reads
    rounded = controls["state_bf16"]
    assert over(dict(rounded, rows_length_off=0)) == ["greedy_regret_max"]
    assert rounded["logit_err_forced_median"] < numbers[
        "logit_err_forced_median"]
    assert 0.0 < rounded["state_err_p99"] < 2 * numbers["state_err_p99"]


@pytest.mark.parametrize("control", CONTROLS)
def test_each_control_is_not_correct(tiny_run, monkeypatch, control):
    monkeypatch.setenv("PERFBENCH_CONTROL", control)
    out = parts.load_file(CHECK).compare(job_of(tiny_run[1]), TINY)
    failed = over(out["numbers"])
    assert failed and out["info"]["control"] == control
    if control in ("wrong_unit", "state_bf16"):
        assert failed == ["greedy_regret_max"]
    if control == "stale_state":
        assert {"route_flip_forced_start_share", "state_err_p99"} <= set(
            failed)
    if control == "no_shared":
        assert "logit_err_forced_median" in failed


@pytest.mark.parametrize("what", ["logit", "unit", "frame", "state"])
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
    elif what == "frame":
        dump["units"] = dump["units"][:-1]
    else:
        dump["state"] = dump["state"] * 1.2
    with open(path, "wb") as f:
        np.savez(f, **dump)
    numbers = parts.load_file(CHECK).compare(job_of(kept), TINY)["numbers"]
    assert over(numbers)
    if what == "frame":
        assert numbers["rows_length_off"] == 1
    if what == "state":
        assert over(numbers) == ["state_err_p99"]


# -- readers on a recorded run ----------------------------------------------

def group(steps, live, touched, held_touched, fullest, kv):
    state = 2 * 8683520 * live
    return {"name": "dispatch", "start": 10.0, "end": 10.4, "attrs": {
        "kind": "step", "steps": steps, "slots": 256,
        "live_slot_steps": live, "kv_positions": kv, "layers": [1, 3, 6, 8],
        "assignments": [6 * live] * 4, "experts_touched": touched,
        "held_assignments": [3 * live + 100, 3 * live - 100, 3 * live,
                             3 * live],
        "held_experts_touched": held_touched,
        "max_expert_assignments": fullest, "expert_matmul": "grouped",
        "ssm_layers": 4, "ssm_state_bytes": state,
        "host_ms": {"launch": 64.0, "admit": 96.0, "retire": 32.0}}}


def recorded_run() -> dict:
    spans = [group(32, 8128, [4000] * 4, [2040, 2048, 2000, 2016],
                   [1500] * 4, 8128 * 500),
             group(32, 8000, [3900] * 4, [2000] * 4, [1400] * 4, 8000 * 480),
             {"name": "dispatch", "start": 10.1, "end": 10.12,
              "attrs": {"kind": "prefill", "rows": 1, "ssm_chunks": 4}},
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
    modules = [{"name": f"jit_nemotron_step({k})", "dur_ns": 20e6}
               for k in range(30)]
    modules += [{"name": "jit_nemotron_prefill(7)", "dur_ns": 8e6}] * 10
    modules += [{"name": "jit_unit_vocode(9)", "dur_ns": 5e6}] * 8
    return {"spans": spans, "dims": nemotrongen.describe(REAL)["dims"],
            "device": {"kind": "TPU v5 lite"},
            "metrics_before": {"sonata_runtime_cold_compiles_total": 1.0},
            "metrics_after": {"sonata_runtime_cold_compiles_total": 1.0},
            "cache_entries_added": 0,
            "profile": {"wall_start": 9.5},
            "trace": {"busy_s": 0.8, "window_s": 1.0, "wall_t0": 10.2,
                      "modules": modules}}


def cost_of(groups: list) -> dict:
    n = sum(g["steps"] for g in groups)
    return nemotron_costs.step_cost(
        nemotrongen.backbone(REAL),
        sum(g["live_slot_steps"] for g in groups) / n,
        sum(sum(g["held_experts_touched"]) for g in groups) / n,
        sum(sum(g["held_assignments"]) for g in groups) / n,
        sum(g["kv_positions"] for g in groups) / n)


def wanted(name: str) -> float:
    both = [s["attrs"] for s in recorded_run()["spans"][:2]]
    if name == "device.step_roofline.hybrid":
        # only the group that ended inside the traced interval (10.2-11.2)
        # says what the traced steps did
        cost = cost_of(both[:1])
        return 100.0 * max(cost["ops"] / 197e12,
                           cost["bytes"] / 819e9) / 0.020
    state = 2 * 8683520 * (8128 + 8000) / 64
    return 100.0 * state / cost_of(both)["bytes"]


WANTED = {
    "ar.rows_per_step.hybrid": 252.0,
    "ar.empty_slot_share.hybrid": 100.0 * (1 - 16128 / 16384),
    "ar.host_ms_per_step.hybrid": 6.0,
    "ar.vocode_padding_share.hybrid": 100.0 * (1 - 658 / 768),
    "ar.finish_ms_per_row.hybrid": 1.0,
    "ar.prefill_device_share.hybrid": 100.0 * 0.08 / 0.8,
    "ar.vocode_device_share.hybrid": 100.0 * 0.04 / 0.8,
    "ar.step_ms.hybrid": 20.0,
    "ssm.state_share_of_step_bytes.hybrid": None,
    "moe.experts_touched_per_step.hybrid": (8104 + 8000) / 256,
    "moe.rows_per_expert.hybrid": 12 * 16128 / 16104,
    "moe.held_assignment_share.hybrid": 50.0,
    "moe.max_expert_load_share.hybrid": 100.0 * 11600 / (24 * 16128),
    "moe.grouped_step_share.hybrid": 100.0,
    "device.step_roofline.hybrid": None,
    "device.idle_share.hybrid": 20.0,
    "text.phonemize_ms_per_req.hybrid": 3.0,
    "warmup.cold_compiles_in_window.hybrid": 0.0,
}


@pytest.mark.parametrize("name", sorted(WANTED))
def test_each_new_reader_on_a_recorded_run(name):
    read = parts.load_reader(ROOT, ["perfbench"], name)
    want = WANTED[name] if WANTED[name] is not None else wanted(name)
    assert 0.0 <= want < 260.0
    assert read(recorded_run()) == pytest.approx(want, rel=1e-9)
    # a program without the spans or the programs (the parent): nothing
    # to read, nothing raised
    bare = dict(recorded_run(), spans=[], trace={}, metrics_after={},
                metrics_before={})
    if not name.startswith("warmup."):
        assert read(bare) is None
    # a sibling's spans, which state neither state nor held experts
    older = recorded_run()
    for s in older["spans"][:2]:
        for key in ("held_assignments", "held_experts_touched",
                    "ssm_state_bytes", "ssm_layers"):
            del s["attrs"][key]
    if name.split(".")[0] in ("ssm", "moe") and "grouped" not in name \
            and "max_expert" not in name or "roofline" in name:
        assert read(older) is None
    entry = [m for m in json.loads((ROOT / "BENCHMARK.json").read_text())[
        "per_layer"] if m["name"] == name]
    assert entry and entry[0]["workloads"] == [CELL] \
        and entry[0]["moves"] == "audio_s_per_s"


def test_the_roofline_of_the_recorded_run_is_bound_by_bytes():
    cost = cost_of([recorded_run()["spans"][0]["attrs"]])
    assert cost["bytes"] / 819e9 > 5 * cost["ops"] / 197e12
    assert 0.0 < wanted("device.step_roofline.hybrid") < 100.0
    # the issue's picture of a step: state two fifths, experts half
    assert 0.38 < cost["state_bytes"] / cost["bytes"] < 0.42
    assert 0.44 < cost["expert_bytes"] / cost["bytes"] < 0.50
    assert 10.5e9 < cost["bytes"] < 11.2e9


def test_the_costs_against_a_hand_count_and_the_issues_arithmetic():
    z = nemotron_costs.sizes(nemotrongen.backbone(TINY))
    # hidden 64, 8 heads of 8, 2 groups of 16, convolution 4, 128 channels
    assert z["mamba"] == 64 * (64 + 128 + 8) + 5 * 128 + 3 * 8 + 64 \
        + 64 * 64 == 17624
    assert z["attn"] == 64 * (4 + 2 * 2) * 16 + 4 * 16 * 64 == 12288
    assert (z["expert"], z["shared"], z["router"]) == (
        2 * 64 * 24, 2 * 64 * 48, 65 * 8)
    assert (z["mamba_layers"], z["attn_layers"], z["expert_layers"]) == (
        3, 1, 3)
    assert z["state"] == 64 * 16 + 3 * 128
    cost = nemotron_costs.step_cost(
        nemotrongen.backbone(TINY), live_rows=3, held_experts_touched=7,
        held_assignments=9, kv_positions=100)
    fixed = 3 * 17624 + 12288 + 3 * (520 + 6144) + 8 * 64 + 512 * 64
    assert cost["state_bytes"] == 2 * 4 * 3 * 1408 * 3
    assert cost["expert_bytes"] == 2 * 7 * 3072
    assert cost["bytes"] == 2 * fixed + cost["expert_bytes"] \
        + cost["state_bytes"] + 2 * 2 * 1 * 32 * 100 + 4 * 3 * 512
    active = 3 * 17624 + 12288 + 3 * (520 + 6144) + 512 * 64
    assert cost["ops"] == 2.0 * active * 3 + 2.0 * 3072 * 9 \
        + 6.0 * 3 * 1408 * 3 + 2.0 * 2 * 1 * 64 * 100
    # the published sizes, by the issue's own count (millions)
    z = nemotron_costs.sizes(nemotrongen.backbone(REAL))
    assert round(z["mamba"] / 1e6, 2) == 38.74
    assert round(z["attn"] / 1e6, 2) == 23.40
    assert round(z["expert"] / 1e6, 2) == 9.98      # at 1856 columns
    assert round(z["shared"] / 1e6, 2) == 19.96
    assert round(z["head"] / 1e6, 1) == 352.3
    assert 4 * 4 * z["state"] == 8683520    # float32, four layers
    specs = nemotrongen.tensor_specs(REAL)
    held = sum(int(np.prod(s[1])) for s in specs if s[0] != "unit_table")
    assert round(held / 1e6) == 3519


def test_the_traffic_is_the_siblings_with_256_callers():
    sibling = json.loads((ROOT / "perfbench/traffic/batch.sentence.json")
                         .read_text())
    mine = json.loads((ROOT / "perfbench/traffic/batch.sentence256.json")
                      .read_text())
    assert (sibling["callers"], mine["callers"]) == (64, 256)
    assert "batch.sentence.json with callers 256" in mine["source"]
    assert "busy hour" in mine["source"]
    for key in set(sibling) | set(mine):
        if key not in ("callers", "source"):
            assert mine[key] == sibling[key], key
    assert mine["kind"] == "closed_paragraphs" and len(
        mine["paragraphs"]) == 128


def test_the_configuration_keeps_every_published_number():
    published = {
        "attention_bias": False, "chunk_size": 128, "conv_kernel": 4,
        "expand": 2, "head_dim": 128, "hidden_size": 2688,
        "intermediate_size": 1856, "layer_norm_epsilon": 1e-05,
        "mamba_head_dim": 64, "mamba_hidden_act": "silu",
        "mamba_num_heads": 64, "mamba_proj_bias": False,
        "max_position_embeddings": 262144, "mlp_bias": False,
        "mlp_hidden_act": "relu2", "model_type": "nemotron_h",
        "moe_intermediate_size": 1856,
        "moe_shared_expert_intermediate_size": 3712, "n_group": 1,
        "n_groups": 8, "n_shared_experts": 1, "norm_eps": 1e-05,
        "norm_topk_prob": True, "num_attention_heads": 32,
        "num_experts_per_tok": 6, "num_key_value_heads": 2,
        "num_logits_to_keep": 1, "partial_rotary_factor": 1,
        "rescale_prenorm_residual": True, "residual_in_fp32": False,
        "rope_theta": 10000, "routed_scaling_factor": 2.5,
        "sliding_window": None, "ssm_state_size": 128,
        "tie_word_embeddings": False, "time_step_floor": 0.0001,
        "time_step_max": 0.1, "time_step_min": 0.001, "topk_group": 1,
        "use_bias": False, "use_conv_bias": True, "use_mamba_kernels": True,
        "vocab_size": 131072}
    for key, value in published.items():
        assert REAL[key] == value, key
    whole = "MEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEMEM*EMEMEMEME"
    assert REAL["published"] == {"num_hidden_layers": 52,
                                 "hybrid_override_pattern": whole,
                                 "n_routed_experts": 128}
    assert len(whole) == 52 and (whole.count("M"), whole.count("E"),
                                 whole.count("*")) == (23, 23, 6)
    assert REAL["num_hidden_layers"] == 9
    assert REAL["hybrid_override_pattern"] == whole[:9] == "MEMEM*EME"
    assert REAL["n_routed_experts"] == 64
    assert REAL["expert_parallel"]["routed_experts"] == 128
    assert REAL["expert_parallel"]["held"] == [0, 64]
    reduced = ["num_hidden_layers", "hybrid_override_pattern",
               "n_routed_experts"]
    assert set(REAL["reduced"]) - {"why"} == set(reduced)
    assert "3519 M = 7.04 GB" in REAL["reduced"]["why"]
    for said in ("two chips share each layer's routed experts",
                 "held = (0, 64)", "experts at half their rows",
                 "24 assignment rows an expert", "stage 0"):
        assert said in REAL["deployment"], said
    for key in ("no_position_encoding", "id_split", "unit_table", "frame",
                "generator", "length_rule", "sampling", "weights", "slots",
                "state_precision", "expert_layout", "warm_up", "residual"):
        assert key in REAL["assumed"], key
    assert REAL["voice"]["units"] == {"first_id": 256, "stop_id": 131071,
                                      "frames_per_id": 3.5}
    assert REAL["server"]["env"]["SONATA_AR_SLOTS"] == "256"
    assert REAL["server"]["argv"][-2:] == ["--max-in-flight", "256"]
    assert REAL_FILE.parent.name == "nemotron"
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    (entry,) = [c for c in bench["configs"] if c["name"] == CONFIG]
    assert entry == {
        "name": CONFIG, "source": REAL["source"],
        "file": "perfbench/configs/nemotron/nemotron-3-nano-30b-a3b.json",
        "reduced": reduced, "why": entry["why"]}
    assert "layers 0-8 of 52, experts 0-63 of 128 a layer" in entry["source"]
    cells = [w for w in bench["workloads"] if w["config"] == CONFIG]
    assert [(w["name"], w["traffic"], w["chips"]) for w in cells] == [
        (CELL, "batch.sentence256", 1)]
    assert len(cells[0]["why"]) <= 200
    assert "experts at half their rows" in cells[0]["why"]
    # the generator, the traffic's voice block and the seed are the
    # siblings', key for key
    lfm2 = json.loads((ROOT / "perfbench/configs/lfm2/lfm2-24b-a2b.json")
                      .read_text())
    assert REAL["voice"]["model"] == lfm2["voice"]["model"]
    assert {k: v for k, v in REAL["voice"].items() if k != "units"} == {
        k: v for k, v in lfm2["voice"].items() if k != "units"}
    assert REAL["weights"] == lfm2["weights"]
    assert nemotrongen.describe(REAL)["samples_per_frame"] == 256


def test_the_reference_imports_nothing_of_the_program_and_sets_highest():
    source = (ROOT / "perfbench/reference/nemotron_ref.py").read_text()
    imported = set()
    for node in ast.walk(ast.parse(source)):
        if isinstance(node, ast.Import):
            imported.update(a.name.split(".")[0] for a in node.names)
        elif isinstance(node, ast.ImportFrom):
            imported.add((node.module or "").split(".")[0])
    assert imported == {"__future__", "jax"}
    assert 'default_matmul_precision(HIGHEST)' in source
    assert 'HIGHEST = "highest"' in source and "lax.scan" in source


def test_every_limit_lies_between_its_two_readings_with_its_reason():
    limits = json.loads((ROOT / "perfbench/reference/limits"
                         / f"{CONFIG}.json").read_text())
    reasons = json.loads((ROOT / "perfbench/reference/limits"
                          / f"{CONFIG}.reasons.json").read_text())
    named = [k for k, v in limits.items() if v is not None]
    assert set(named) == {
        "audio_err_max", "audio_err_ratio_median", "rows_length_off",
        "logit_err_median", "logit_err_p99", "route_flip_share",
        "logit_err_forced_median", "logit_err_forced_p99",
        "route_flip_forced_share", "route_flip_forced_start_share",
        "state_err_p99", "greedy_regret_max"}
    assert parts.load_limits(ROOT, PATHS, CONFIG) == {
        k: limits[k] for k in named}
    for key in named:
        r = reasons[key]
        assert r["why"] and r["control"] in CONTROLS + ("an altered dump",)
        if key == "rows_length_off":
            continue
        assert r["sound_max"] < limits[key] < r["control_reads"], key
