"""The block-diffusion cell's files on the CPU: the tiny configuration of
the same family end to end through the same writer, server command,
reference, comparison and readers as ``sdar-30b-a3b``; the comparison's
three controls; the packed reference against passes run one by one; every
new reader on a recorded run; the cost file against a hand count; the
limits and the configuration's file."""

import json
import shutil
from pathlib import Path

import numpy as np
import pytest

from perfbench import run
from perfbench.harness import parts, sdar_costs, sdargen

ROOT = Path(__file__).resolve().parent.parent.parent
DATA = Path(__file__).resolve().parent / "data"
BENCH = DATA / "sdar-tiny-benchmark.json"
TINY = json.loads((DATA / "sdar-tiny.json").read_text())
REAL_FILE = ROOT / "perfbench/configs/sdar/sdar-30b-a3b.json"
REAL = json.loads(REAL_FILE.read_text())
CELL = "sdar-30b-a3b.batch.sentence"
PATHS = ["perfbench", "tests/perfbench"]
SEED = 3000000007
CHECK = ROOT / "perfbench/reference/sdar_check.py"


@pytest.fixture(scope="module")
def tiny_run(tmp_path_factory):
    """One traced run of the tiny cell; what the timed path left for the
    comparison is kept, so that the controls need no second server."""
    kept = tmp_path_factory.mktemp("sdar_kept")

    def keep(done):
        work = Path(done["sampled_audio"]).parent
        shutil.copytree(work / "ar_dump", kept / "ar_dump")
        shutil.copy(done["sampled_audio"], kept / "sampled_audio.npz")
        (kept / "done.json").write_text(json.dumps(done["sampled"]))

    out = run.run_cell("sdar-tiny.sentence", SEED, 2.0, True,
                       benchmark_file=BENCH, platform="cpu",
                       require_accelerator=False, alter_audio=keep)
    return out, kept


def job_of(kept: Path) -> dict:
    return {"root": str(ROOT), "paths": PATHS,
            "config_file": "tests/perfbench/data/sdar-tiny.json",
            "seed": SEED, "words": "perfbench/traffic/words.tsv",
            "sampled": json.loads((kept / "done.json").read_text()),
            "sampled_audio": str(kept / "sampled_audio.npz"),
            "work_dir": str(kept)}


def over(numbers: dict) -> list:
    """The limits a set of numbers breaks."""
    limits = parts.load_limits(ROOT, PATHS, "sdar-tiny")
    return sorted(k for k, v in limits.items()
                  if numbers.get(k) is None or numbers[k] > v)


def test_the_tiny_cell_is_correct_end_to_end(tiny_run):
    out, kept = tiny_run
    assert out["correct"] is True and out["failed"] == 0 < out["attempted"]
    assert set(out["compared"]) == {
        "audio_err_max", "rows_length_off", "logit_err_median",
        "logit_err_p99", "route_flip_share", "logit_err_forced_median",
        "logit_err_forced_p99", "route_flip_forced_share",
        "unmask_regret_max"}
    assert out["compared"]["rows_length_off"]["value"] == 0
    # span metrics are read on the CPU too; device metrics are left out
    metrics = {k: v["value"] for k, v in out["metrics"].items()}
    assert set(metrics) == {
        "diff.units_per_row_pass.blocks", "diff.commit_pass_share.blocks",
        "diff.rows_per_pass.blocks", "diff.empty_slot_share.blocks",
        "diff.host_ms_per_pass.blocks",
        "moe.experts_touched_per_pass.blocks", "moe.rows_per_expert.blocks",
        "moe.max_expert_load_share.blocks",
        "warmup.cold_compiles_in_window.blocks",
        "text.phonemize_ms_per_req.blocks",
        "diff.vocode_padding_share.blocks", "diff.finish_ms_per_row.blocks"}
    # six callers over four slots: every pass is full; a block of four
    # units every three passes, less the first and last blocks' waste
    assert metrics["diff.rows_per_pass.blocks"] == 4.0
    assert 1.2 < metrics["diff.units_per_row_pass.blocks"] < 4 / 3
    assert 33.0 < metrics["diff.commit_pass_share.blocks"] < 33.7
    assert 2.0 <= metrics["moe.experts_touched_per_pass.blocks"] <= 8.0
    assert 16 * 2 / 8 <= metrics["moe.rows_per_expert.blocks"] <= 16.0
    # the text stage, the vocoder's frame buckets and the finisher run here
    # as in the sibling cell
    assert metrics["text.phonemize_ms_per_req.blocks"] > 0.0
    assert 0.0 <= metrics["diff.vocode_padding_share.blocks"] < 100.0
    assert metrics["diff.finish_ms_per_row.blocks"] > 0.0
    info = out["info"]
    assert info["rows"] == info["rows_compared"] == 6
    kept_passes = 6 * info["kept_passes_a_row"]
    assert info["positions_compared"] == 4 * kept_passes >= 4 * 6 * 6
    # every kept denoising pass that unmasked a position has its regret
    assert 0.5 * kept_passes < info["passes_compared"] <= kept_passes * 2 / 3
    assert len(list((kept / "ar_dump").glob("pb-check-*.npz"))) == 6
    json.dumps(out)


def test_the_kept_dump_is_judged_as_the_run_was(tiny_run, monkeypatch):
    out, kept = tiny_run
    monkeypatch.setenv("PERFBENCH_ALSO_CONTROLS",
                       "reference_fp8,no_commit,causal_block")
    compared = parts.load_file(CHECK).compare(job_of(kept), TINY)
    numbers, info = compared["numbers"], compared["info"]
    assert not over(numbers) and info["numbers"] == numbers
    for name, c in out["compared"].items():
        assert numbers[name] == pytest.approx(c["value"], rel=1e-3, abs=1e-6)
    # on the served routes no near-tie stands between the two (which rows
    # are replayed follows the run's timing, and a handful of flips may miss
    # the kept passes: never the wider of the two, not always the narrower)
    assert numbers["logit_err_forced_p99"] <= numbers["logit_err_p99"]
    assert info["unmask_regret_own_max"] >= 0.0
    # the controls' numbers beside the sound run's, the run left as it is
    controls = info["controls"]
    assert sorted(controls) == ["causal_block", "no_commit", "reference_fp8"]
    for name, low in controls.items():
        assert over(low) and set(low) >= set(numbers) - {"rows_length_off"}
    assert controls["reference_fp8"]["audio_err_max"] \
        > 10 * numbers["audio_err_max"]
    # a cache that kept a denoising pass's keys and values is far off on
    # any routes; the precision below and the wrong mask by less
    assert controls["no_commit"]["logit_err_forced_median"] > 0.3
    assert controls["reference_fp8"]["logit_err_forced_median"] > 0.05
    assert controls["causal_block"]["logit_err_forced_median"] \
        > 5 * numbers["logit_err_forced_median"]


@pytest.mark.parametrize("control,breaks", [
    ("reference_fp8", {"logit_err_median", "logit_err_forced_median",
                       "route_flip_share", "unmask_regret_max",
                       "audio_err_max"}),
    ("no_commit", {"logit_err_median", "logit_err_forced_median",
                   "logit_err_forced_p99", "unmask_regret_max"}),
    ("causal_block", {"logit_err_median", "logit_err_forced_median",
                      "logit_err_forced_p99"})])
def test_each_control_is_not_correct(tiny_run, monkeypatch, control, breaks):
    monkeypatch.setenv("PERFBENCH_CONTROL", control)
    out = parts.load_file(CHECK).compare(job_of(tiny_run[1]), TINY)
    assert set(over(out["numbers"])) >= breaks
    assert out["info"]["control"] == control
    assert out["numbers"]["rows_length_off"] == 0


def test_an_unknown_control_is_refused(tiny_run, monkeypatch):
    monkeypatch.setenv("PERFBENCH_CONTROL", "wrong_unit")
    with pytest.raises(ValueError, match="no control"):
        parts.load_file(CHECK).compare(job_of(tiny_run[1]), TINY)


@pytest.mark.parametrize("what", ["logit", "unit", "frame", "seen", "mask"])
def test_an_altered_dump_is_not_correct(tiny_run, tmp_path, what):
    kept = tmp_path / "kept"
    shutil.copytree(tiny_run[1], kept)
    path = sorted((kept / "ar_dump").glob("*.npz"))[0]
    with np.load(path) as f:
        dump = {k: f[k] for k in f.files}
    n = len(dump["ids"])
    if what == "logit":
        dump["logits"][:, :, 300] += 100.0
    elif what == "unit":
        # one committed unit read as another: every later block saw it
        dump["tokens"][n + 1] = 256 + (dump["tokens"][n + 1] - 255) % 200
        for m, launch in enumerate(dump["passes"]):
            start = n // 4 * 4 + launch // 3 * 4
            if start <= n + 1 < start + 4 and dump["seen"][m][
                    n + 1 - start] != 510:
                dump["seen"][m][n + 1 - start] = dump["tokens"][n + 1]
    elif what == "frame":
        dump["tokens"] = dump["tokens"][:-4]
    elif what == "seen":
        dump["seen"][1] = dump["seen"][2]
    else:
        dump["tokens"][-1] = 510
    with open(path, "wb") as f:
        np.savez(f, **dump)
    numbers = parts.load_file(CHECK).compare(job_of(kept), TINY)["numbers"]
    assert over(numbers)
    if what in ("frame", "seen", "mask"):
        assert numbers["rows_length_off"] == 1
    if what == "unit":
        assert numbers["unmask_regret_max"] > 0.1


def test_the_packed_reference_is_the_passes_run_one_by_one(tiny_run):
    """A kept denoising pass's block beside the committed tokens, at the
    block's positions and seeing what it would see alone, gives the logits
    of the whole pass over prefix + block; a commit pass's are read off the
    committed tokens themselves."""
    import jax
    import jax.numpy as jnp

    check = parts.load_file(CHECK)
    ref = parts.load_file(ROOT / "perfbench/reference/sdar_ref.py")
    job = job_of(tiny_run[1])
    row = [r for r in check.load_rows(job, TINY, ROOT)
           if check.sound_row(r, TINY, 16)][0]
    packed = check.pack(row, TINY)
    assert len(packed["tokens"]) == len(row["tokens"]) + 4 * sum(
        p["pass"] < 2 for p in row["passes"])
    assert len(packed["at"]) == 4 * len(row["passes"])
    bb = sdargen.backbone(TINY)
    wide = lambda tree: jax.tree_util.tree_map(  # noqa: E731
        lambda a: a.astype(jnp.float32), tree)
    embed, head, norm_f = (wide(sdargen.draw(TINY, name))
                           for name in ("embed", "head", "norm_f"))
    layers = [wide(sdargen.draw_layer(TINY, i)) for i in range(3)]

    def through(tokens, visible, positions):
        with jax.default_matmul_precision("highest"):
            h = embed[jnp.asarray(tokens)]
            for p in layers:
                h, _ = ref.layer(h, p, bb, jnp.asarray(visible),
                                 jnp.asarray(positions))
            return np.asarray(ref.head(h, head, norm_f, bb))

    together = through(packed["tokens"], packed["visible"],
                       packed["positions"])[packed["at"]]
    for m, p in enumerate(row["passes"]):
        alone = np.concatenate([row["tokens"][:p["start"]], p["seen"]])
        with jax.default_matmul_precision("highest"):
            want, _ = ref.forward(jnp.asarray(alone), bb, 4, embed, head,
                                  norm_f, lambda i: layers[i])
        np.testing.assert_allclose(together[4 * m:4 * m + 4],
                                   np.asarray(want)[p["start"]:],
                                   atol=2e-4, rtol=2e-4)
    # the stand-in of ``no_commit``: every generated block as its last
    # denoising pass saw it, and all three passes of a kept block beside it
    dirty = check.pack(row, TINY, stand_in=True)
    n = len(row["ids"])
    assert (dirty["tokens"][n:len(row["tokens"])] == 510).sum() \
        == (row["unmasked_at"] == 1).sum() > 0
    assert len(dirty["tokens"]) == len(row["tokens"]) + 4 * len(
        row["passes"])
    causal = check.pack(row, TINY, causal=True)
    assert np.array_equal(causal["visible"][:9, :9],
                          np.tril(np.ones((9, 9), bool)))
    assert not np.array_equal(causal["visible"], packed["visible"])


# -- readers on a recorded run ----------------------------------------------

def group(steps, live, units, commits, touched, fullest, kv):
    return {"name": "dispatch", "start": 10.0, "end": 10.4, "attrs": {
        "kind": "step", "steps": steps, "slots": 64, "block_length": 4,
        "denoising_steps": 2, "live_slot_steps": live, "units": units,
        "positions": 4 * live, "commit_row_passes": commits,
        "denoise_row_passes": live - commits, "kv_positions": kv,
        "layers": [0, 1], "assignments": [32 * live, 32 * live],
        "experts_touched": touched, "max_expert_assignments": fullest,
        "host_ms": {"launch": 48.0, "admit": 4.0, "retire": 12.0}}}


def recorded_run() -> dict:
    spans = [group(32, 2048, 2700, 683, [4090, 4096], [1300, 1340],
                   2048 * 500),
             group(32, 1984, 2600, 661, [4000, 4050], [1200, 1250],
                   1984 * 480),
             {"name": "dispatch", "start": 10.1, "end": 10.12,
              "attrs": {"kind": "prefill", "rows": 1, "blocks": 30,
                        "tail_ids": 2}},
             {"name": "dispatch", "start": 10.3, "end": 10.31,
              "attrs": {"kind": "vocode", "rows": 1, "frames_needed": 420,
                        "frames_bucket": 512, "fetch_wait_ms": 3.0,
                        "finish_ms": 1.5}},
             {"name": "phonemize", "start": 10.0, "end": 10.004,
              "attrs": {}},
             {"name": "encode-ids", "start": 10.004, "end": 10.005,
              "attrs": {}}]
    spans[1].update(start=12.0, end=12.4)
    modules = [{"name": f"jit_sdar_pass({k})", "dur_ns": 25e6}
               for k in range(24)]
    modules += [{"name": "jit_sdar_prefill(7)", "dur_ns": 28e6}] * 5
    modules += [{"name": "jit_unit_vocode(9)", "dur_ns": 10e6}] * 4
    modules += [{"name": "jit_lfm2_step(3)", "dur_ns": 1e6}]
    return {"spans": spans, "dims": sdargen.describe(REAL)["dims"],
            "device": {"kind": "TPU v5 lite"},
            "metrics_before": {"sonata_runtime_cold_compiles_total": 1.0},
            "metrics_after": {"sonata_runtime_cold_compiles_total": 1.0},
            "cache_entries_added": 0,
            "profile": {"wall_start": 9.5},
            "trace": {"busy_s": 0.8, "window_s": 1.0, "wall_t0": 10.2,
                      "modules": modules}}


def wanted_step_roofline() -> float:
    cost = sdar_costs.pass_cost(sdargen.backbone(REAL), 4, 64.0,
                                (4090 + 4096) / 32, 2048 * 500 / 32)
    seconds = max(cost["ops"] / 197e12, cost["bytes"] / 819e9)
    assert cost["bytes"] / 819e9 > cost["ops"] / 197e12
    return 100.0 * seconds / 0.025


WANTED = {
    "diff.units_per_row_pass.blocks": 5300 / 4032,
    "diff.commit_pass_share.blocks": 100.0 * 1344 / 4032,
    "diff.rows_per_pass.blocks": 63.0,
    "diff.empty_slot_share.blocks": 100.0 * (1 - 4032 / 4096),
    "diff.host_ms_per_pass.blocks": 2.0,
    "diff.pass_ms.blocks": 25.0,
    "diff.prefill_device_share.blocks": 100.0 * 0.14 / 0.8,
    "diff.vocode_device_share.blocks": 100.0 * 0.04 / 0.8,
    "moe.experts_touched_per_pass.blocks": 16236 / 128,
    "moe.rows_per_expert.blocks": 64 * 4032 / 16236,
    "moe.max_expert_load_share.blocks": 100.0 * 5090 / (64 * 4032),
    "device.step_roofline.blocks": None,
    "device.idle_share.blocks": 20.0,
    "warmup.cold_compiles_in_window.blocks": 0.0,
    "text.phonemize_ms_per_req.blocks": 5.0,
    "diff.vocode_padding_share.blocks": 100.0 * (1 - 420 / 512),
    "diff.finish_ms_per_row.blocks": 1.5,
}


@pytest.mark.parametrize("name", sorted(WANTED))
def test_each_new_reader_on_a_recorded_run(name):
    read = parts.load_reader(ROOT, ["perfbench"], name)
    want = WANTED[name]
    if want is None:
        # only the group that ended inside the traced interval (10.2-11.2)
        # says what the traced passes did
        want = wanted_step_roofline()
        assert 0.0 < want < 100.0
    assert read(recorded_run()) == pytest.approx(want, rel=1e-9)
    # a program without the spans or the programs (the parent): nothing
    # to read, nothing raised
    bare = dict(recorded_run(), spans=[], trace={}, metrics_after={},
                metrics_before={})
    if not name.startswith("warmup."):
        assert read(bare) is None
    # the parent's step groups carry no ``units``: nothing, not a KeyError
    old = recorded_run()
    for s in old["spans"]:
        for key in ("units", "commit_row_passes", "positions"):
            s["attrs"].pop(key, None)
    old["trace"]["modules"] = [m for m in old["trace"]["modules"]
                               if "sdar" not in m["name"]]
    if name in ("diff.units_per_row_pass.blocks",
                "diff.commit_pass_share.blocks"):
        assert read(old) is None
    else:
        read(old)
    entry = [m for m in json.loads((ROOT / "BENCHMARK.json").read_text())[
        "per_layer"] if m["name"] == name]
    assert entry and entry[0]["workloads"] == [CELL] \
        and entry[0]["moves"] == "audio_s_per_s"


def test_the_costs_against_a_hand_count():
    z = sdar_costs.sizes(sdargen.backbone(TINY))
    assert z["attn"] == 64 * (4 + 2 * 2) * 32 + 4 * 32 * 64 + 2 * 32 == 24640
    assert z["expert"] == 3 * 64 * 32 and z["router"] == 64 * 8
    assert z["head"] == 512 * 64 and z["layers"] == 3
    cost = sdar_costs.pass_cost(sdargen.backbone(TINY), 4, live_rows=3,
                                experts_touched=3 * 5, kv_positions=100)
    fixed = 3 * (24640 + 512 + 128) + 32768 + 64
    assert cost["bytes"] == 2 * (fixed + 15 * 6144) \
        + 2 * 2 * 3 * 64 * 100 + 4 * 12 * 512
    active = 3 * (24640 + 512 + 2 * 6144) + 32768
    assert cost["ops"] == 2.0 * active * 12 + 2.0 * 2 * 3 * 128 * 100 * 4
    # the published sizes, by the issue's own count
    z = sdar_costs.sizes(sdargen.backbone(REAL))
    assert z["expert"] == 4718592 and z["head"] == 311164928
    layer = z["attn"] + z["router"] + z["norms"]
    assert round(layer / 1e6, 2) == 19.14
    assert round((layer + 128 * z["expert"]) / 1e6, 1) == 623.1
    specs = sdargen.tensor_specs(REAL)
    held = sum(int(np.prod(s[1])) for s in specs if s[0] != "unit_table")
    assert round(held / 1e9, 2) == 4.36
    # a pass of the cell: 8.6 GB, bytes-bound
    cost = sdar_costs.pass_cost(sdargen.backbone(REAL), 4, 64, 6 * 128,
                                64 * 500)
    assert 8.5e9 < cost["bytes"] < 8.8e9
    assert cost["bytes"] / 819e9 > cost["ops"] / 197e12


def test_every_limit_stands_with_its_reason():
    limits = json.loads((ROOT / "perfbench/reference/limits/"
                         "sdar-30b-a3b.json").read_text())
    reasons = json.loads((ROOT / "perfbench/reference/limits/"
                          "sdar-30b-a3b.reasons.json").read_text())
    named = {k for k, v in limits.items() if v is not None}
    assert named == set(reasons) == {
        "rows_length_off", "logit_err_median", "logit_err_p99",
        "route_flip_share", "logit_err_forced_median",
        "logit_err_forced_p99", "route_flip_forced_share",
        "unmask_regret_max", "audio_err_max", "audio_err_ratio_median"}
    assert all(len(text) > 40 for text in reasons.values())
    assert parts.load_limits(ROOT, PATHS, "sdar-30b-a3b") == {
        k: limits[k] for k in named}


def test_the_configuration_keeps_every_published_number():
    published = {
        "attention_bias": False, "decoder_sparse_step": 1, "head_dim": 128,
        "hidden_act": "silu", "hidden_size": 2048, "intermediate_size": 6144,
        "max_position_embeddings": 32768, "max_window_layers": 48,
        "mlp_only_layers": [], "model_type": "sdar_moe",
        "moe_intermediate_size": 768, "norm_topk_prob": True,
        "num_attention_heads": 32, "num_experts": 128,
        "num_experts_per_tok": 8, "num_key_value_heads": 4,
        "rms_norm_eps": 1e-06, "rope_scaling": None, "rope_theta": 1000000,
        "sliding_window": None, "tie_word_embeddings": False,
        "use_sliding_window": False, "vocab_size": 151936}
    for key, value in published.items():
        assert REAL[key] == value, key
    assert REAL["num_hidden_layers"] == 6
    assert REAL["published"] == {"num_hidden_layers": 48}
    assert set(REAL["reduced"]) - {"why"} == {"num_hidden_layers"}
    assert "eight pipeline stages of six layers" in REAL["deployment"]
    assert "held = (0, 128)" in REAL["deployment"]
    for key in ("block_length", "mask_id", "no_shift", "commit_pass",
                "schedule", "denoising_steps", "dynamic_rule_not_served",
                "qk_norm", "id_split", "weights", "slots"):
        assert key in REAL["assumed"], key
    assert REAL["voice"]["units"] == {
        "first_id": 256, "stop_id": 151935, "frames_per_id": 3.5,
        "mask_id": 151669, "block_length": 4, "denoising_steps": 2}
    assert REAL_FILE.parent.name == "sdar"
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    assert bench["configs"][-1] == {
        "name": "sdar-30b-a3b", "source": REAL["source"],
        "file": "perfbench/configs/sdar/sdar-30b-a3b.json",
        "reduced": ["num_hidden_layers"],
        "why": bench["configs"][-1]["why"]}
    cell = bench["workloads"][-1]
    assert (cell["name"], cell["config"], cell["traffic"], cell["chips"]) \
        == (CELL, "sdar-30b-a3b", "batch.sentence", 1)
    assert len(cell["why"]) <= 200
    assert [w["name"] for w in bench["workloads"]].count(CELL) == 1
    tiny = json.loads(BENCH.read_text())["per_layer"]
    assert [m["name"] for m in bench["per_layer"][-len(tiny):]] == [
        m["name"] for m in tiny]
    # the generator, the traffic's voice block and the seed are the
    # sibling's, key for key
    lfm2 = json.loads((ROOT / "perfbench/configs/lfm2/lfm2-24b-a2b.json")
                      .read_text())
    assert REAL["voice"]["model"] == lfm2["voice"]["model"]
    assert REAL["voice"]["phoneme_id_map"] == lfm2["voice"]["phoneme_id_map"]
    assert REAL["weights"] == lfm2["weights"]
    assert REAL["server"]["env"] == lfm2["server"]["env"]
    assert sdargen.describe(REAL)["samples_per_frame"] == 256
