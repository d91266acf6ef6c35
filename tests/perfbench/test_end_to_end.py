"""One cell end to end on the CPU with the tiny voice: the stock server is
spawned, warmed by the cell's traffic, measured, stopped and compared with
the reference.  Also the controls: a broken timed path and the precision
below have to come out as not correct, and a run that finds no accelerator
reports nothing."""

import json
from pathlib import Path

import numpy as np
import pytest

from perfbench import run

DATA = Path(__file__).resolve().parent / "data"
TINY = DATA / "tiny-benchmark.json"


def cell(workload="tiny.paragraph", seed=3000000007, trace=False, **kw):
    return run.run_cell(workload, seed, 2.0, trace, benchmark_file=TINY,
                        platform="cpu", require_accelerator=False, **kw)


@pytest.mark.parametrize("workload,trace", [("tiny.paragraph", False),
                                            ("tiny-multi.paragraph", True)])
def test_sound_run_is_correct_and_keeps_the_contract(workload, trace):
    out = cell(workload, trace=trace)
    assert list(out)[:5] == ["correct", "attempted", "failed", "metrics",
                             "device"] and list(out)[-1] == "compared"
    assert out["correct"] is True and out["failed"] == 0 < out["attempted"]
    assert out["device"]["platform"] == "cpu" and out["device"]["count"] >= 1
    assert "memory_peak_bytes" in out["device"]
    wanted = {"text.phonemize_ms_per_req.batch",
              "sched.rows_per_dispatch.batch",
              "warmup.cold_compiles_in_window.batch"} if trace else {
        "audio_s_per_s", "setup_s"}
    assert set(out["metrics"]) == wanted
    # no accelerator: no device metric is reported, not a zero
    assert "busy_s" not in out["device"] and "breakdown" not in out
    for m in out["metrics"].values():
        assert isinstance(m["value"], float) and m["unit"]
    if not trace:
        assert out["metrics"]["audio_s_per_s"]["value"] > 0
    for c in out["compared"].values():
        assert c["value"] <= c["limit"]
    assert out["info"]["rows"] == 8 and out["info"]["frames_compared"] > 100
    # the sample is drawn over the whole window, and the stand-in histogram
    # counts every request the window finished
    assert len(set(out["info"]["sampled_seqs"])) == 2
    # warm-up ran on until the traffic file's minimum of requests
    assert out["info"]["warmup"]["requests"] >= 12
    replay = out["info"]["estimator_replay"]
    assert sum(replay["frame_buckets"].values()) == out["info"]["completed"]
    json.dumps(out)


def test_an_altered_answer_is_not_correct():
    def negate_one_row(done):
        with np.load(done["sampled_audio"]) as f:
            arrays = {k: f[k] for k in f.files}
        first = sorted(arrays)[0]
        arrays[first] = (-arrays[first].astype(np.int32)).clip(
            -32768, 32767).astype("<i2")
        with open(done["sampled_audio"], "wb") as f:
            np.savez(f, **arrays)

    out = cell(alter_audio=negate_one_row)
    assert out["correct"] is False
    assert out["compared"]["audio_err_max"]["value"] > 1.0


def test_dropped_frames_are_not_correct():
    def drop_frames(done):
        with np.load(done["sampled_audio"]) as f:
            arrays = {k: f[k] for k in f.files}
        for k in arrays:
            arrays[k] = arrays[k][:-80]     # five tiny-voice frames each
        with open(done["sampled_audio"], "wb") as f:
            np.savez(f, **arrays)

    out = cell(alter_audio=drop_frames)
    assert out["correct"] is False
    assert out["compared"]["rows_unaligned"]["value"] > 0


def test_the_precision_below_is_not_correct(monkeypatch):
    """The control: the program's own bfloat16 decoder path."""
    monkeypatch.setenv("SONATA_COMPUTE_DTYPE", "bfloat16")
    out = cell()
    assert out["correct"] is False and out["failed"] == 0
    c = out["compared"]["audio_err_max"]
    assert c["value"] > 3 * c["limit"] / 2


def test_no_accelerator_no_result(capsys, monkeypatch):
    with pytest.raises(run.HarnessError, match="not on an accelerator"):
        run.run_cell("tiny.paragraph", 1, 1.0, False, benchmark_file=TINY,
                     platform="cpu")
    # the command itself: JAX finds no TPU here, so the server never starts
    rc = run.main(["--workload", "lessac-high.batch.paragraph", "--seed",
                   "1", "--seconds", "1", "--trace", "0"])
    assert rc != 0 and capsys.readouterr().out == ""


def test_the_reference_in_fp8_is_not_correct(monkeypatch):
    """The control that runs at the cells' own size on the chip: the
    reference put in the program's place, stored in float8."""
    monkeypatch.setenv("PERFBENCH_CONTROL", "reference_fp8")
    out = cell("tiny-multi.paragraph")
    assert out["correct"] is False and out["failed"] == 0
    c = out["compared"]["audio_err_max"]
    assert c["value"] > 3 * c["limit"]
