"""The seams a configuration of another architecture is added through, as
files alone: the voice's writer, the reference, the comparison and the
limits are found by name (``harness/parts.py``), the comparison is handed the
replayed requests' spans and the run's work directory, and the server can
be pointed at that directory.  The ``seam`` configuration has all of its
parts under ``tests/perfbench/`` and none under ``perfbench/``: if it needs
an edit there to pass, the seam is not done."""

import json
import subprocess
from pathlib import Path

import numpy as np
import pytest

from perfbench import run
from perfbench.harness import parts, server
from perfbench.reference import check

ROOT = Path(__file__).resolve().parents[2]
DATA = Path(__file__).resolve().parent / "data"
PATHS = ["perfbench", "tests/perfbench"]
SEED = 3000000011


def config_of(file: str) -> dict:
    return json.loads((ROOT / file).read_text())


@pytest.fixture(scope="module")
def seam_run():
    return run.run_cell("seam.paragraph", SEED, 2.0, False,
                        benchmark_file=DATA / "seam-benchmark.json",
                        platform="cpu", require_accelerator=False)


def test_the_seam_cell_is_correct_on_numbers_and_limits_of_its_own(seam_run):
    out = seam_run
    assert out["correct"] is True and out["failed"] == 0 < out["attempted"]
    assert list(out)[-1] == "compared"
    limits = json.loads((ROOT / "tests/perfbench/reference/limits/seam.json")
                        .read_text())
    # the default's rows_unaligned, the file's own names, and no number the
    # file took out
    assert set(out["compared"]) == {"rows_unaligned"} | {
        k for k, v in limits.items() if v is not None}
    for name, c in out["compared"].items():
        assert c["value"] is not None and c["value"] <= c["limit"], name
    assert out["compared"]["seam_audio_err_max"]["value"] > 0


def test_the_comparison_gets_the_replayed_requests_spans_untraced(seam_run):
    info = seam_run["info"]
    assert info["seam_spans_of"] == sorted(
        f"pb-check-{SEED}-{seq}" for seq in info["sampled_seqs"])
    # every span of the request, the root under the RPC's name among them
    assert {"SynthesizeUtterance", "phonemize", "dispatch",
            "stream-emit"} <= set(info["seam_span_names"])
    assert seam_run["compared"]["seam_replayed_without_dispatch"][
        "value"] == 0


def test_the_writers_marker_and_the_servers_are_read_through_work_dir(
        seam_run):
    # the writer's marker under <work_dir>/voice, the server's under the
    # directory its argv and its env were pointed at, the reference's name
    assert seam_run["compared"]["seam_files_astray"] == {"value": 0,
                                                         "limit": 0}


def test_a_voice_without_a_frame_budget_estimator_has_no_replay_of_one(
        seam_run):
    assert "estimator_replay" not in seam_run["info"]
    assert seam_run["info"]["completed"] > 0


def test_the_seam_cell_is_not_correct_under_a_lowered_limit():
    out = run.run_cell("seam-strict.paragraph", SEED, 2.0, False,
                       benchmark_file=DATA / "seam-benchmark.json",
                       platform="cpu", require_accelerator=False)
    assert out["correct"] is False and out["failed"] == 0
    over = {k for k, c in out["compared"].items() if c["value"] > c["limit"]}
    assert over == {"seam_audio_err_max"}


def test_no_file_under_perfbench_knows_of_the_seam():
    for file in (ROOT / "perfbench").rglob("*"):
        if file.is_file() and file.suffix in (".py", ".json"):
            assert "seam" not in file.read_text().lower(), file


@pytest.mark.parametrize("file", ["perfbench/configs/lessac-high.json",
                                  "perfbench/configs/libritts-high.json",
                                  "tests/perfbench/data/tiny.json"])
def test_a_configuration_without_the_keys_resolves_to_the_vits_files(file):
    config = config_of(file)
    assert "writer" not in config and "check" not in config
    got = {key: Path(parts.load(ROOT, PATHS, config, key).__file__)
           for key in parts.DEFAULTS}
    assert got == {"writer": ROOT / "perfbench/harness/voicegen.py",
                   "reference": ROOT / "perfbench/reference/vits_ref.py",
                   "check": ROOT / "perfbench/reference/vits_check.py"}
    # and the same without "reference", which these files do name
    del config["reference"]
    assert parts.load(ROOT, PATHS, config, "reference") is parts.load_file(
        ROOT / "perfbench/reference/vits_ref.py")
    described = parts.load(ROOT, PATHS, config, "writer").describe(config)
    assert described["frame_budget_estimator"] is True
    assert described["num_speakers"] == config["voice"]["num_speakers"]
    assert described["samples_per_frame"] == int(np.prod(
        described["dims"]["upsample_rates"]))


@pytest.mark.parametrize("file", ["perfbench/run.py",
                                  "perfbench/harness/loadgen.py",
                                  "perfbench/harness/server.py",
                                  "perfbench/reference/check.py"])
def test_the_harness_names_no_vits_file(file):
    text = (ROOT / file).read_text()
    for name in ("vits_ref", "vits_check", "voicegen", "model_dims",
                 "MODEL_DEFAULTS", "build_params"):
        assert name not in text, (file, name)


def test_a_named_file_outside_the_paths_is_refused(tmp_path):
    (tmp_path / "elsewhere.py").write_text("def compare(job, config): ...")
    with pytest.raises(parts.PartError, match="under none of"):
        parts.load(ROOT, PATHS, {"check": str(tmp_path / "elsewhere.py")},
                   "check")
    with pytest.raises(parts.PartError, match="no file"):
        parts.load(ROOT, PATHS, {"check": "perfbench/reference/absent.py"},
                   "check")


def test_the_dispatcher_loads_a_comparison_from_a_second_paths_directory(
        tmp_path):
    second = tmp_path / "second"
    second.mkdir()
    (second / "own_check.py").write_text(
        "def compare(job, config):\n"
        "    return {'numbers': {'own_gap': 0.25},\n"
        "            'info': {'config': config['name'],\n"
        "                     'work_dir': job['work_dir']}}\n")
    (second / "own.json").write_text(json.dumps(
        {"name": "own", "check": str(second / "own_check.py")}))
    job = {"root": str(ROOT), "paths": ["perfbench", str(second)],
           "config_file": str(second / "own.json"), "work_dir": "w",
           "sampled": [{"seq": 0, "rid": "r", "ok": True}]}
    out = check.run_check(job)
    assert out["numbers"] == {"own_gap": 0.25}
    assert out["info"] == {"config": "own", "work_dir": "w"}
    assert out["device"]["platform"] == "cpu" and "trace" not in out
    # no request to compare: no comparison is called, and no number stands
    none = check.run_check(dict(job, sampled=[{"seq": 0, "ok": False}]))
    assert none["numbers"] == {} and "no request" in none["error"]


@pytest.mark.parametrize("name,want", [
    # limits.json's default alone
    ("other", {"audio_err_max": 0.04, "ratio": 1.24, "rows_unaligned": 0}),
    # its own entry for the configuration over the default; null removes
    ("listed", {"audio_err_max": 0.002, "rows_unaligned": 0}),
    # the configuration's file over both: a null removes, a new name adds
    ("filed", {"audio_err_max": 0.01, "logit_gap_max": 0.5}),
    ("listed-and-filed", {"rows_unaligned": 0, "logit_gap_max": 2.0}),
])
def test_limits_are_layered_and_a_null_takes_a_number_out(tmp_path, name,
                                                          want):
    first, second = tmp_path / "a" / "reference", tmp_path / "b" / "reference"
    first.mkdir(parents=True)
    (second / "limits").mkdir(parents=True)
    (first / "limits.json").write_text(json.dumps({
        "default": {"audio_err_max": 0.04, "ratio": 1.24,
                    "rows_unaligned": 0},
        "listed": {"audio_err_max": 0.002, "ratio": None},
        "listed-and-filed": {"audio_err_max": 0.002, "ratio": None}}))
    (second / "limits" / "filed.json").write_text(json.dumps(
        {"audio_err_max": 0.01, "ratio": None, "rows_unaligned": None,
         "logit_gap_max": 0.5}))
    (second / "limits" / "listed-and-filed.json").write_text(json.dumps(
        {"audio_err_max": None, "logit_gap_max": 2.0}))
    assert parts.load_limits(tmp_path, ["a", "b"], name) == want


def test_the_benchmarks_own_limits_read_as_they_did():
    assert parts.load_limits(ROOT, PATHS, "lessac-high") == {
        "audio_err_max": 0.04, "audio_err_ratio_median": 1.24,
        "rows_unaligned": 0}
    assert parts.load_limits(ROOT, PATHS, "tiny") == {
        "audio_err_max": 0.002, "rows_unaligned": 0}


def test_work_dir_is_filled_in_the_servers_argv_and_env(tmp_path):
    config = {"server": {
        "argv": ["-c", "import os, sys; open(sys.argv[1], 'w').write("
                       "os.environ['POINTED_AT'] + ' ' + sys.argv[2])",
                 "{work_dir}/saw.txt", "{voice}:{grpc_port}"],
        "env": {"POINTED_AT": "{work_dir}/bulky"}}}
    s = server.Server(ROOT, config, "v.json", "cpu", tmp_path)
    try:
        assert s.proc.wait(timeout=60.0) == 0
    finally:
        s.stop()
    assert (tmp_path / "saw.txt").read_text() == \
        f"{tmp_path}/bulky v.json:{s.grpc_port}"


def test_the_moved_comparison_reads_what_the_parents_read():
    """The golden: the replayed requests of one run of the parent commit,
    the audio it was answered, and the numbers the comparison gave there,
    before it was moved to a file the configuration names."""
    golden = json.loads((DATA / f"golden-tiny-{SEED}.json").read_text())
    config = config_of("tests/perfbench/data/tiny.json")
    job = {"root": str(ROOT), "paths": PATHS, "seed": golden["seed"],
           "config_file": "tests/perfbench/data/tiny.json",
           "words": "perfbench/traffic/words.tsv",
           "sampled": golden["sampled"], "speaker": golden["speaker"],
           "sampled_audio": str(DATA / f"golden-tiny-{SEED}.npz"),
           "sampled_spans": {}, "work_dir": "", "rows": None,
           "limits": parts.load_limits(ROOT, PATHS, "tiny")}
    out = parts.load(ROOT, PATHS, config, "check").compare(job, config)
    assert set(out["numbers"]) >= set(golden["numbers"])
    for name, want in golden["numbers"].items():
        assert out["numbers"][name] == pytest.approx(want, rel=1e-6), name
    for name, want in golden["info"].items():
        got = out["info"][name]
        assert got == (pytest.approx(want, rel=1e-6)
                       if isinstance(want, float) else want), name


def test_the_check_child_and_the_named_files_load_without_jax():
    """``run.py`` loads writers and never imports jax; the check child sets
    the compile cache before the comparison compiles anything."""
    code = ("import sys; from pathlib import Path; "
            "from perfbench import run; from perfbench.harness import parts; "
            "import perfbench.reference.check as c; "
            "[parts.load(Path.cwd(), ['perfbench'], {}, k) "
            " for k in ('writer', 'check')]; "
            "print(callable(c.main), callable(run.run_cell), "
            "'jax' in sys.modules)")
    out = subprocess.run(["python3", "-c", code], cwd=ROOT, timeout=120,
                         capture_output=True, text=True)
    assert out.stdout.split() == ["True", "True", "False"], out.stderr
