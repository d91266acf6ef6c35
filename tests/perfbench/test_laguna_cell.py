"""The windowed cell's files on the CPU: the tiny configuration of the same
family (a window of 8 places) end to end through the same writer, server
command, reference, comparison and readers as ``laguna-xs.2``; the
comparison's seven controls; every new reader on a recorded run; the cost
file against a hand count and against the issue's arithmetic; the
configuration against the catalog's row.  Entries of ``BENCHMARK.json`` are
found by name and a metric's ``workloads`` by membership: no position is
pinned."""

import ast
import json
import shutil
from pathlib import Path

import numpy as np
import pytest

from perfbench import run
from perfbench.harness import laguna_costs, lagunagen, parts

ROOT = Path(__file__).resolve().parent.parent.parent
DATA = Path(__file__).resolve().parent / "data"
BENCH = DATA / "laguna-tiny-benchmark.json"
TINY = json.loads((DATA / "laguna-tiny.json").read_text())
REAL_FILE = ROOT / "perfbench/configs/laguna/laguna-xs.2.json"
REAL = json.loads(REAL_FILE.read_text())
CONFIG = "laguna-xs.2"
CELL = "laguna-xs.2.batch.sentence256"
CHECK = ROOT / "perfbench/reference/laguna_check.py"
CATALOG = Path("/opt/skills/guides/model-configs/architectures.jsonl")
PATHS = ["perfbench", "tests/perfbench"]
SEED = 3000000007
FAULTS = ("no_window", "sliding_rule_in_full_layers", "no_yarn_factor",
          "no_gate", "stale_ring")
CONTROLS = ("reference_bf16", "wrong_unit") + FAULTS
COMPARED = {
    "audio_err_max", "rows_length_off", "logit_err_median", "logit_err_p99",
    "route_flip_share", "logit_err_forced_median", "logit_err_forced_p99",
    "route_flip_forced_share", "route_flip_forced_start_share",
    "greedy_regret_max"}
#: the cell's per-layer metrics that are the windowed readers' own, and
#: those it shares with every unit cell
WINDOWED = (
    "ar.rows_per_step", "ar.empty_slot_share", "ar.host_ms_per_step",
    "ar.step_ms", "ar.prefill_device_share", "ar.vocode_device_share",
    "ar.vocode_padding_share", "ar.finish_ms_per_row",
    "moe.experts_touched_per_step", "moe.rows_per_expert",
    "moe.held_assignment_share", "moe.max_expert_load_share",
    "moe.grouped_step_share", "text.phonemize_ms_per_req",
    "device.idle_share", "device.step_roofline",
    "attn.reader_roofline", "attn.cache_share_of_step_bytes",
    "attn.window_bound_share")
SHARED = ("warmup.compile_s_before_window",
          "warmup.cache_load_share_before_window",
          "warmup.compiles_after_ready", "loop.device_wait_share",
          "loop.turn_ms_max")
#: what only a device trace gives (the CPU runs the einsum: no kernel)
DEVICE = ("ar.step_ms", "ar.prefill_device_share", "ar.vocode_device_share",
          "device.idle_share", "device.step_roofline",
          "attn.reader_roofline")


@pytest.fixture(scope="module")
def tiny_run(tmp_path_factory):
    """One traced run of the tiny cell; what the timed path left for the
    comparison is kept, so that the controls need no second server."""
    kept = tmp_path_factory.mktemp("laguna_kept")

    def keep(done):
        work = Path(done["sampled_audio"]).parent
        shutil.copytree(work / "ar_dump", kept / "ar_dump")
        shutil.copy(done["sampled_audio"], kept / "sampled_audio.npz")
        (kept / "done.json").write_text(json.dumps(done["sampled"]))

    out = run.run_cell("laguna-tiny.sentence", SEED, 2.0, True,
                       benchmark_file=BENCH, platform="cpu",
                       require_accelerator=False, alter_audio=keep)
    return out, kept


def job_of(kept: Path) -> dict:
    return {"root": str(ROOT), "paths": PATHS,
            "config_file": "tests/perfbench/data/laguna-tiny.json",
            "seed": SEED, "words": "perfbench/traffic/words.tsv",
            "sampled": json.loads((kept / "done.json").read_text()),
            "sampled_audio": str(kept / "sampled_audio.npz"),
            "work_dir": str(kept)}


def over(numbers: dict) -> list:
    """The limits ``numbers`` pass."""
    limits = parts.load_limits(ROOT, PATHS, "laguna-tiny")
    return sorted(k for k, v in limits.items()
                  if numbers.get(k) is None or numbers[k] > v)


def test_the_tiny_cell_is_correct_end_to_end(tiny_run):
    out, kept = tiny_run
    assert out["correct"] is True and out["failed"] == 0 < out["attempted"]
    assert set(out["compared"]) == COMPARED
    assert out["compared"]["rows_length_off"]["value"] == 0
    # span and counter metrics are read on the CPU too; device metrics and
    # the kernel's roofline are left out
    assert set(out["metrics"]) == {
        f"{name}.windowed" for name in WINDOWED if name not in DEVICE
    } | set(SHARED)
    value = {k: v["value"] for k, v in out["metrics"].items()}
    # six callers over four slots: every step is full
    assert value["ar.rows_per_step.windowed"] == 4.0
    # two of eight experts held: about a quarter of the assignments
    assert 5.0 < value["moe.held_assignment_share.windowed"] < 60.0
    assert 0.5 <= value["moe.experts_touched_per_step.windowed"] <= 2.0
    assert 0.0 < value["attn.cache_share_of_step_bytes.windowed"] < 100.0
    # a window of 8: every step past a prompt of 12 and more ids is bound
    assert value["attn.window_bound_share.windowed"] > 90.0
    assert value["moe.grouped_step_share.windowed"] == 0.0    # the CPU
    info = out["info"]
    assert info["rows"] == info["rows_compared"] == info["rows_wrapped"] == 6
    assert info["steps_compared"] == info["frames_compared"] > 500
    dumps = sorted((kept / "ar_dump").glob("pb-check-*.npz"))
    assert len(dumps) == 6
    with np.load(dumps[0]) as f:
        # experts 0-7 in unsigned bytes, logits over the vocabulary
        assert f["routes"].dtype == np.uint8 and f["routes"].max() < 8
        assert f["routes"].shape[1:] == (4, 2)
        assert f["logits"].shape[1] == 512
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
    assert numbers["route_flip_forced_share"] <= numbers["route_flip_share"]
    # one wrong unit in one row moves the regret and nothing else
    wrong = controls["wrong_unit"]
    assert wrong["greedy_regret_max"] > 0.5
    assert {k: v for k, v in wrong.items()
            if not k.startswith("greedy_regret")} == {
        k: v for k, v in dict(numbers, **info).items()
        if k in wrong and not k.startswith("greedy_regret")}
    # a window left open, a rotary rule swapped, a factor or a gate
    # dropped: nothing is near
    for name in FAULTS[:-1]:
        assert controls[name]["logit_err_forced_median"] > 10 * numbers[
            "logit_err_forced_median"], name
    # a stale ring of 8 places shows below position 8 alone, before any
    # logit is kept (a prompt has 12 ids and more): in what the first
    # positions chose (the cell's ring of 512 covers most of a row)
    stale = controls["stale_ring"]
    assert stale["route_flip_forced_start_share"] > 5 * max(
        numbers["route_flip_forced_start_share"], 0.005)
    assert stale["route_flip_forced_start_share"] > stale[
        "route_flip_forced_share"]
    # the precision below moves the arithmetic's numbers by less than a
    # fault does: its routes are not the served ones, and the regret says so
    low = controls["reference_bf16"]
    assert low["logit_err_forced_median"] > 1.2 * numbers[
        "logit_err_forced_median"]
    assert low["route_flip_share"] > 1.2 * numbers["route_flip_share"]
    assert "greedy_regret_max" in over(dict(low, rows_length_off=0))


@pytest.mark.parametrize("control", CONTROLS)
def test_each_control_is_not_correct(tiny_run, monkeypatch, control):
    monkeypatch.setenv("PERFBENCH_CONTROL", control)
    out = parts.load_file(CHECK).compare(job_of(tiny_run[1]), TINY)
    failed = over(out["numbers"])
    assert failed and out["info"]["control"] == control
    if control == "wrong_unit":
        assert failed == ["greedy_regret_max"]
    if control in FAULTS[:-1]:
        assert "logit_err_forced_median" in failed
    if control == "stale_ring":
        assert "route_flip_forced_start_share" in failed


@pytest.mark.parametrize("what", ["logit", "unit", "frame", "route"])
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
        dump["routes"] = (dump["routes"] + 3) % 8
    with open(path, "wb") as f:
        np.savez(f, **dump)
    numbers = parts.load_file(CHECK).compare(job_of(kept), TINY)["numbers"]
    assert over(numbers)
    if what == "frame":
        assert numbers["rows_length_off"] == 1
    if what == "route":
        assert "route_flip_share" in over(numbers)


# -- readers on a recorded run ----------------------------------------------

BB = lagunagen.backbone(REAL)
PLACE = 4096            # 8 heads of 128, keys and values, bfloat16


def held_bytes(kv: int, ring: int) -> int:
    """A group's ``kv_cache_bytes``: two full layers, six rings."""
    return PLACE * (2 * kv + 6 * ring)


def group(steps, live, touched, held_touched, held, fullest, kv, ring,
          bound):
    return {"name": "dispatch", "start": 10.0, "end": 10.4, "attrs": {
        "kind": "step", "steps": steps, "slots": 256,
        "live_slot_steps": live, "kv_positions": kv,
        "layers": [1, 2, 3, 4, 5, 6, 7],
        "assignments": [8 * live] * 7, "experts_touched": touched,
        "held_assignments": held, "held_experts_touched": held_touched,
        "max_expert_assignments": fullest, "expert_matmul": "grouped",
        "attention": "slot_kernel", "ssm_layers": 0, "ssm_state_bytes": 0,
        "latent_layers": 0, "latent_cache_bytes": 0,
        "full_layers": 2, "window_layers": 6, "window": 512,
        "kv_cache_bytes": held_bytes(kv, ring),
        "window_bound_row_steps": bound, "held_overflow_steps": 0,
        "host_ms": {"launch": 64.0, "admit": 96.0, "retire": 32.0}}}


def recorded_run() -> dict:
    spans = [group(32, 8128, [7000] * 7, [1000, 1024, 990, 1024, 1024, 1010,
                                          1024],
                   [8000, 8100, 8050, 7990, 8080, 8032, 8100], [900] * 7,
                   8128 * 350, 8128 * 335, 1300),
             group(32, 8000, [6900] * 7, [1024] * 7, [8000] * 7, [800] * 7,
                   8000 * 340, 8000 * 330, 1280),
             {"name": "dispatch", "start": 10.1, "end": 10.12,
              "attrs": {"kind": "prefill", "rows": 1, "admit": "step",
                        "window_layers": 6}},
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
    modules = [{"name": f"jit_laguna_step({k})", "dur_ns": 16e6}
               for k in range(24)]
    modules += [{"name": "jit_laguna_step_admit(3)", "dur_ns": 20e6}] * 6
    modules += [{"name": "jit_unit_vocode(9)", "dur_ns": 5e6}] * 8
    # the reader's eight operations, a layer each, among the heaviest
    ops = [[f"%slot_attention.{k} = f32[256,8,16,128]", 0.04 + 0.001 * k]
           for k in range(8)]
    ops += [["%grouped_matmul.3 = f32[640,1024]", 0.02],
            ["%fusion.9 = f32[256,100352]", 0.015]]
    return {"spans": spans, "dims": lagunagen.describe(REAL)["dims"],
            "device": {"kind": "TPU v5 lite"},
            "metrics_before": {"sonata_runtime_cold_compiles_total": 1.0},
            "metrics_after": {"sonata_runtime_cold_compiles_total": 1.0},
            "cache_entries_added": 0,
            "profile": {"wall_start": 9.5, "log_dir": "/nonexistent"},
            "trace": {"busy_s": 0.8, "window_s": 1.0, "wall_t0": 10.2,
                      "modules": modules, "device_ops": ops}}


def mean_of(groups: list) -> tuple:
    n = sum(g["steps"] for g in groups)
    return (sum(g["live_slot_steps"] for g in groups) / n,
            sum(sum(g["held_experts_touched"]) for g in groups) / n,
            sum(sum(g["held_assignments"]) for g in groups) / n,
            sum(g["kv_positions"] for g in groups) / n,
            sum(g["kv_cache_bytes"] for g in groups) / n)


def wanted(name: str) -> float:
    both = [s["attrs"] for s in recorded_run()["spans"][:2]]
    # only the group that ended inside the traced interval (10.2-11.2) says
    # what the traced steps did
    traced = mean_of(both[:1])
    if name == "device.step_roofline.windowed":
        cost = laguna_costs.step_cost(BB, *traced)
        return 100.0 * max(cost["ops"] / 197e12, cost["bytes"] / 819e9) \
            * 30 / (24 * 0.016 + 6 * 0.020)
    if name == "attn.reader_roofline.windowed":
        cost = laguna_costs.attention_cost(BB, traced[0], traced[3],
                                           traced[4])
        kernel = sum(0.04 + 0.001 * k for k in range(8))
        return 100.0 * max(cost["ops"] / 197e12, cost["bytes"] / 819e9) \
            * 30 / kernel
    held = (held_bytes(8128 * 350, 8128 * 335)
            + held_bytes(8000 * 340, 8000 * 330)) / 64
    return 100.0 * held / laguna_costs.step_cost(BB, *mean_of(both))["bytes"]


WANTED = {
    "ar.rows_per_step.windowed": 252.0,
    "ar.empty_slot_share.windowed": 100.0 * (1 - 16128 / 16384),
    "ar.host_ms_per_step.windowed": 6.0,
    "ar.vocode_padding_share.windowed": 100.0 * (1 - 658 / 768),
    "ar.finish_ms_per_row.windowed": 1.0,
    "ar.prefill_device_share.windowed": 0.0,
    "ar.vocode_device_share.windowed": 100.0 * 0.04 / 0.8,
    "ar.step_ms.windowed": (24 * 16.0 + 6 * 20.0) / 30,
    "attn.cache_share_of_step_bytes.windowed": None,
    "attn.reader_roofline.windowed": None,
    "attn.window_bound_share.windowed": 100.0 * 2580 / 16128,
    "moe.experts_touched_per_step.windowed": (7096 + 7168) / (64 * 7),
    "moe.rows_per_expert.windowed": (56352 + 56000) / (7096 + 7168),
    "moe.held_assignment_share.windowed": 100.0 * 112352 / (56 * 16128),
    "moe.max_expert_load_share.windowed": 100.0 * 11900 / (56 * 16128),
    "moe.grouped_step_share.windowed": 100.0,
    "device.step_roofline.windowed": None,
    "device.idle_share.windowed": 20.0,
    "text.phonemize_ms_per_req.windowed": 3.0,
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
    assert read(bare) is None
    # a sibling's spans, which state no window layers
    older = recorded_run()
    for s in older["spans"][:2]:
        for key in ("full_layers", "window_layers", "window",
                    "kv_cache_bytes", "window_bound_row_steps"):
            del s["attrs"][key]
    if name.split(".")[0] == "attn" or "roofline" in name:
        assert read(older) is None
    (entry,) = [m for m in json.loads((ROOT / "BENCHMARK.json").read_text())[
        "per_layer"] if m["name"] == name]
    assert entry["workloads"] == [CELL] \
        and entry["moves"] == "audio_s_per_s"
    if "roofline" in name:
        assert (entry["unit"], entry["source"]) == ("%", "device_trace")


def test_the_kernels_time_is_read_by_name_or_not_at_all():
    from perfbench.harness import windowed

    recorded = recorded_run()
    assert windowed.kernel_seconds(recorded) == pytest.approx(
        sum(0.04 + 0.001 * k for k in range(8)))
    # fewer than a layer each among the ten heaviest, and no profile on
    # disk: nothing tells the kernel apart
    recorded["trace"]["device_ops"] = recorded["trace"]["device_ops"][3:]
    assert windowed.kernel_seconds(recorded) is None
    read = parts.load_reader(ROOT, ["perfbench"],
                             "attn.reader_roofline.windowed")
    assert read(recorded) is None
    # an einsum has no name of its own
    recorded["trace"]["device_ops"] = [["%fusion.1 = f32[256,8,8,128]", 0.1]]
    assert read(recorded) is None


def test_the_roofline_of_the_recorded_run_is_the_issues_picture():
    traced = mean_of([recorded_run()["spans"][0]["attrs"]])
    cost = laguna_costs.step_cost(BB, *traced)
    # bytes-bound: 2.5 GB of weights read and 2.8 GB of cache as held
    assert (cost["ops"] / 197e12) / (cost["bytes"] / 819e9) < 1.0
    assert 0.0 < wanted("device.step_roofline.windowed") < 100.0
    assert 0.0 < wanted("attn.reader_roofline.windowed") < 100.0
    assert 2.7e9 < cost["cache_bytes"] < 2.9e9
    assert 2.4e9 < cost["bytes"] - cost["cache_bytes"] - 4 * 254 * 100352 \
        < 2.6e9
    assert 0.45 < cost["cache_bytes"] / cost["bytes"] < 0.55
    # the rings' places come back out of the bytes
    assert laguna_costs.ring_places(BB, traced[3], traced[4]) == \
        pytest.approx(8128 * 335 / 32)
    # a full layer's read: 6 query heads a key-value head, 2 x 2 x 128
    # operations a place and query head over 4096 bytes: 6 an operation a
    # byte of cache, far under the chip's ridge of 240
    full = laguna_costs.attention_cost(
        dict(BB, num_hidden_layers=1), 0.0, 1000.0, 4096 * 1000.0)
    assert full["ops"] / full["bytes"] == 48 * 4 * 128 / 4096 == 6.0


def test_the_costs_against_a_hand_count_and_the_issues_arithmetic():
    tiny = lagunagen.backbone(TINY)
    z = laguna_costs.sizes(tiny)
    # hidden 64, 2 key-value heads of 16, 4 | 6 query heads
    assert z["attention"] == [
        64 * n * 16 + 2 * 64 * 32 + 64 * n + n * 16 * 64
        for n in (4, 6, 6, 6, 4)] == [12544, 16768, 16768, 16768, 12544]
    assert (z["dense"], z["expert"], z["shared"], z["router"]) == (
        3 * 64 * 96, 3 * 64 * 24, 3 * 64 * 24, 64 * 8)
    assert (z["layers"], z["dense_layers"], z["expert_layers"],
            z["full_layers"], z["window_layers"]) == (5, 1, 4, 2, 3)
    assert (z["full_heads"], z["window_heads"], z["place_bytes"],
            z["window"], z["vocab"]) == (8, 18, 128, 8, 512)
    # three rows that attend over 100 positions together, 20 in a ring
    held = 128 * (2 * 100 + 3 * 20)
    assert laguna_costs.ring_places(tiny, 100, held) == 20
    cost = laguna_costs.step_cost(
        tiny, live_rows=3, held_experts_touched=5, held_assignments=9,
        kv_positions=100, kv_cache_bytes=held)
    fixed = sum(z["attention"]) + 5 * 128 + 18432 + 4 * (512 + 4608) \
        + 512 * 64 + 64
    attention_ops = 2.0 * 2 * 16 * (8 * 100 + 18 * 20)
    assert cost["expert_bytes"] == 2 * 5 * 4608
    assert cost["cache_bytes"] == held
    assert cost["bytes"] == 2 * fixed + cost["expert_bytes"] + held \
        + 4 * 3 * 512
    assert cost["ops"] == 2.0 * fixed * 3 + 2.0 * 4608 * 9 + attention_ops
    assert laguna_costs.attention_cost(tiny, 3, 100, held) == {
        "ops": attention_ops, "bytes": held + 3 * 26 * 16 * 6.0}
    # the published sizes, by the issue's own count (millions)
    z = laguna_costs.sizes(BB)
    assert [round(n / 1e6, 1) for n in z["attention"]] == [
        29.5, 37.9, 37.9, 37.9, 29.5, 37.9, 37.9, 37.9]
    assert round(z["dense"] / 1e6, 1) == 50.3
    assert round(z["expert"] / 1e6, 3) == round(z["shared"] / 1e6, 3) \
        == 3.146
    assert round(z["router"] / 1e6, 1) == 0.5
    assert round(z["head"] / 1e6, 1) == 205.5
    assert (z["place_bytes"], z["window"]) == (4096, 512)
    specs = lagunagen.tensor_specs(REAL)
    held = sum(int(np.prod(s[1])) for s in specs if s[0] != "unit_table")
    # the issue's 1.48 G parameters, norms and all: 2.96 GB in bfloat16
    assert round(held / 1e9, 2) == 1.48 and round(2 * held / 1e9, 2) == 2.96
    # a slot: two whole caches and six rings, 20 MB; 256 of them 5.4 GB
    slot = PLACE * (2 * 1024 + 6 * 512)
    assert slot == 20 * 2 ** 20 and round(256 * slot / 1e9, 2) == 5.37
    assert "2 x 1024 + 6 x 512 places = 20 MB a slot" in REAL["assumed"][
        "slots"]


def test_the_traffic_is_the_hybrid_cells_file_unedited():
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in bench["workloads"]}
    assert cells[CELL]["traffic"] == cells[
        "nemotron-3-nano-30b-a3b.batch.sentence256"]["traffic"] \
        == "batch.sentence256"
    mine = json.loads((ROOT / "perfbench/traffic/batch.sentence256.json")
                      .read_text())
    assert mine["callers"] == 256 and mine["kind"] == "closed_paragraphs"
    # the issue's reckoning of the file: a row of n ids attends over n + 1
    # to n + round(3.5 n) - 1 positions: 70 of the 128 rows pass place 512,
    # the window binds in 16 % of the row-steps, and a row-step reads 348
    # places of a whole cache and 334 of a ring (the issue: 349 and 335)
    ids = [2 * chars + 2 for (chars,) in mine["paragraphs"]]
    assert (len(ids), min(ids), max(ids)) == (128, 68, 182)
    spans = [range(n + 1, n + round(3.5 * n)) for n in ids]
    assert max(r[-1] for r in spans) == 818 < 1024
    assert sum(r[-1] > 512 for r in spans) == 70
    read = [a for r in spans for a in r]
    assert round(100 * sum(a > 512 for a in read) / len(read)) == 16
    assert round(sum(read) / len(read)) == 348
    assert round(sum(min(a, 512) for a in read) / len(read)) == 334


def test_the_configuration_keeps_every_published_number():
    rows = [json.loads(line) for line in CATALOG.read_text().splitlines()
            if '"Laguna-XS.2"' in line] if CATALOG.exists() else []
    reduced = ["num_hidden_layers", "layer_types", "mlp_layer_types",
               "num_attention_heads_per_layer", "num_experts"]
    published = rows[0]["config"] if rows else {
        k: v for k, v in REAL.items() if k in (
            "model_type", "vocab_size", "hidden_size", "intermediate_size",
            "num_attention_heads", "num_key_value_heads", "head_dim",
            "max_position_embeddings", "attention_bias", "rms_norm_eps",
            "num_experts_per_tok", "moe_intermediate_size",
            "shared_expert_intermediate_size", "tie_word_embeddings",
            "gating", "sliding_window", "rope_parameters",
            "moe_apply_router_weight_on_input", "partial_rotary_factor",
            "moe_routed_scaling_factor")}
    if rows:
        assert len(published) == 25
        assert REAL["source"].startswith(rows[0]["source_url"] + "; ")
        for key in ("layer_types", "mlp_layer_types",
                    "num_attention_heads_per_layer"):
            assert REAL[key] == published[key][:8], key
        assert (published["num_hidden_layers"], published["num_experts"]) \
            == (40, 256)
    for key, value in published.items():
        if key not in reduced:
            assert REAL[key] == value, key
    # every width as published
    assert (REAL["hidden_size"], REAL["head_dim"], REAL["sliding_window"],
            REAL["intermediate_size"], REAL["moe_intermediate_size"],
            REAL["shared_expert_intermediate_size"],
            REAL["num_experts_per_tok"], REAL["num_key_value_heads"],
            REAL["vocab_size"]) == (2048, 128, 512, 8192, 512, 512, 8, 8,
                                    100352)
    assert REAL["rope_parameters"]["full_attention"] == {
        "rope_theta": 500000, "rope_type": "yarn", "factor": 64,
        "original_max_position_embeddings": 4096, "beta_slow": 1,
        "beta_fast": 64, "attention_factor": 1.4158883083359672,
        "partial_rotary_factor": 0.5}
    assert REAL["rope_parameters"]["sliding_attention"] == {
        "rope_type": "default", "rope_theta": 10000,
        "partial_rotary_factor": 1}
    assert {k: REAL[k] for k in reduced} == {
        "num_hidden_layers": 8,
        "layer_types": (["full_attention"] + ["sliding_attention"] * 3) * 2,
        "mlp_layer_types": ["dense"] + ["sparse"] * 7,
        "num_attention_heads_per_layer": [48, 64, 64, 64] * 2,
        "num_experts": 32}
    assert (REAL["published"]["num_hidden_layers"],
            REAL["published"]["num_experts"]) == (40, 256)
    # the floors: two whole periods, seven expert layers behind the dense
    # one, 32 experts; the vocabulary whole; no width among what is reduced
    assert REAL["mlp_layer_types"].count("sparse") >= 4
    assert REAL["num_experts"] >= 8
    assert not [k for k in reduced if k.endswith(("_dim", "_rank", "_size"))]
    assert REAL["expert_parallel"]["routed_experts"] == 256
    assert REAL["expert_parallel"]["held"] == [0, 32]
    assert set(REAL["reduced"]) - {"why"} == set(reduced)
    assert "1.48 G parameters = 2.96 GB" in REAL["reduced"]["why"]
    for said in ("8 chips share each layer", "held = (0, 32)",
                 "experts at an eighth of their rows", "stage 0",
                 "No code stands in"):
        assert said in REAL["deployment"], said
    for key in ("router", "gate", "qk_norm", "rotary", "softmax_scale",
                "residual", "unread_keys", "id_split", "unit_table", "frame",
                "generator", "length_rule", "sampling", "weights", "slots",
                "warm_up"):
        assert key in REAL["assumed"], key
    for key in ("precision", "memory"):
        assert isinstance(REAL[key], str) and len(REAL[key]) > 100
    assert REAL["voice"]["units"] == {"first_id": 256, "stop_id": 100351,
                                      "frames_per_id": 3.5}
    assert REAL["server"]["env"]["SONATA_AR_SLOTS"] == "256"
    assert REAL["server"]["env"]["SONATA_AR_POSITIONS"] == "1024"
    assert REAL["server"]["argv"][-2:] == ["--max-in-flight", "256"]
    assert REAL["server"]["argv"][0] == "perfbench/harness/laguna_serve.py"
    assert REAL_FILE.parent.name == "laguna"
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    (entry,) = [c for c in bench["configs"] if c["name"] == CONFIG]
    assert entry == {
        "name": CONFIG, "source": REAL["source"],
        "file": "perfbench/configs/laguna/laguna-xs.2.json",
        "reduced": reduced, "why": entry["why"]}
    assert len(entry["source"]) <= 200 and len(entry["why"]) <= 200
    assert "layers 0-7 of 40, experts 0-31 of 256 a layer" in entry["source"]
    cells = [w for w in bench["workloads"] if w["config"] == CONFIG]
    assert [(w["name"], w["traffic"], w["chips"]) for w in cells] == [
        (CELL, "batch.sentence256", 1)]
    assert len(cells[0]["why"]) <= 200
    for said in ("two cache geometries", "16 % of row-steps",
                 "8 of 40 layers"):
        assert said in cells[0]["why"], said
    # every per-layer entry of the cell, by name and by membership
    mine = {m["name"] for m in bench["per_layer"]
            if CELL in m.get("workloads", [])}
    assert mine == {f"{n}.windowed" for n in WINDOWED}
    # the five shared readers run in the tiny cell above and do not list
    # this one yet: a name may only be appended, and a sibling's test pins
    # the last (PERF.md section 7)
    assert not any(CELL in m["workloads"] for m in bench["per_layer"]
                   if m["name"] in SHARED)
    # the generator, the traffic's voice block and the seed are the
    # siblings', key for key
    lfm2 = json.loads((ROOT / "perfbench/configs/lfm2/lfm2-24b-a2b.json")
                      .read_text())
    assert {k: v for k, v in REAL["voice"].items() if k != "units"} == {
        k: v for k, v in lfm2["voice"].items() if k != "units"}
    assert REAL["weights"] == lfm2["weights"]
    assert lagunagen.describe(REAL)["samples_per_frame"] == 256


def test_the_reference_imports_nothing_of_the_program_and_sets_highest():
    source = (ROOT / "perfbench/reference/laguna_ref.py").read_text()
    imported = set()
    for node in ast.walk(ast.parse(source)):
        if isinstance(node, ast.Import):
            imported.update(a.name.split(".")[0] for a in node.names)
        elif isinstance(node, ast.ImportFrom):
            imported.add((node.module or "").split(".")[0])
    assert imported == {"__future__", "jax", "math"}
    assert 'default_matmul_precision(HIGHEST)' in source
    assert 'HIGHEST = "highest"' in source


def test_every_limit_lies_between_its_two_readings_with_its_reason():
    limits = json.loads((ROOT / "perfbench/reference/limits"
                         / f"{CONFIG}.json").read_text())
    reasons = json.loads((ROOT / "perfbench/reference/limits"
                          / f"{CONFIG}.reasons.json").read_text())
    named = [k for k, v in limits.items() if v is not None]
    assert set(named) <= COMPARED | {"audio_err_ratio_median"}
    assert {"rows_length_off", "logit_err_forced_median",
            "greedy_regret_max", "audio_err_max"} <= set(named)
    for key in set(limits) - set(named) - {"rows_unaligned"}:
        assert "NOT COMPARED" in reasons[key]["why"], key
    assert parts.load_limits(ROOT, PATHS, CONFIG) == {
        k: limits[k] for k in named}
    for key in named:
        r = reasons[key]
        assert r["why"] and r["control"] in CONTROLS + (
            "an altered dump", "reference_bfloat16")
        if key == "rows_length_off":
            continue
        assert r["sound_max"] < limits[key] < r["control_reads"], key
