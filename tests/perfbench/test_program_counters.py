"""The readers of the program's own counters: on built ``run`` objects (the
arithmetic, and a program from before the counters), and once on a real
traced run of the tiny cell on the CPU."""

import json
from pathlib import Path

import pytest

from perfbench import run
from perfbench.harness import parts

ROOT = Path(__file__).resolve().parent.parent.parent
BENCH = Path(__file__).resolve().parent / "data" / \
    "tiny-counters-benchmark.json"
SHARES = ["sched.frame_padding_share.batch", "sched.pad_ragged_share.batch",
          "sched.pad_headroom_share.batch", "sched.pad_bucket_share.batch"]
NEW = SHARES + ["sched.overflow_retries.batch",
                "fetch.host_ms_per_dispatch.batch"]


def reader(name):
    return parts.load_reader(ROOT, ["perfbench", "tests/perfbench"], name)


def page(frames: dict, groups=0.0, retries=0.0, host=None) -> dict:
    """``/metrics`` as the harness parses it, with a label the registry
    might add one day."""
    out = {f'sonata_dispatch_frames_total{{node="a",part="{p}"}}': float(v)
           for p, v in frames.items()}
    out["sonata_dispatch_groups_total"] = float(groups)
    out["sonata_dispatch_overflow_retries_total"] = float(retries)
    for phase, v in (host or {}).items():
        out[f'sonata_dispatch_host_seconds_total{{phase="{phase}"}}'] = v
    out["sonata_requests_total"] = 7.0
    return out


BEFORE = page(dict(served=1000, ragged=300, headroom=400, bucket=300,
                   dummy_rows=0, retried=64), groups=10, retries=1,
              host=dict(enqueue=0.5, fetch_wait=2.0, epilogue=0.1))
AFTER = page(dict(served=1000 + 5300, ragged=300 + 1600, headroom=400 + 1200,
                  bucket=300 + 1500, dummy_rows=0 + 300, retried=64 + 100),
             groups=10 + 20, retries=1 + 2,
             host=dict(enqueue=0.5 + 0.04, fetch_wait=2.0 + 1.8,
                       epilogue=0.1 + 0.06))
BUILT = {"metrics_before": BEFORE, "metrics_after": AFTER}


@pytest.mark.parametrize("name,expect", [
    ("sched.frame_padding_share.batch", 47.0),
    ("sched.pad_ragged_share.batch", 16.0),
    ("sched.pad_headroom_share.batch", 12.0),
    ("sched.pad_bucket_share.batch", 15.0),
    ("sched.overflow_retries.batch", 2.0),
    ("fetch.host_ms_per_dispatch.batch", 5.0),
])
def test_reader_arithmetic_over_the_window(name, expect):
    assert reader(name)(BUILT) == pytest.approx(expect, abs=1e-9)


def test_the_shares_are_one_split():
    total, ragged, headroom, bucket = (reader(n)(BUILT) for n in SHARES)
    dummy_rows, retried = 3.0, 1.0      # 300 and 100 of 10 000 frames
    assert total == pytest.approx(
        ragged + headroom + bucket + dummy_rows + retried, abs=1e-9)


@pytest.mark.parametrize("name", NEW)
def test_a_program_without_the_counters_reads_as_nothing(name):
    old = {"sonata_requests_total": 7.0,
           'sonata_runtime_cold_compiles_total{voice="1"}': 0.0}
    assert reader(name)({"metrics_before": old,
                         "metrics_after": dict(old)}) is None


@pytest.mark.parametrize("name", SHARES + ["fetch.host_ms_per_dispatch.batch"])
def test_an_idle_window_reads_as_nothing(name):
    assert reader(name)({"metrics_before": AFTER,
                         "metrics_after": AFTER}) is None


def test_every_new_metric_is_declared_with_a_reader():
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    declared = {m["name"]: m for m in bench["per_layer"]}
    for name in NEW:
        m = declared[name]
        assert m["source"] == "program_counter"
        assert m["moves"] == "audio_s_per_s"
        assert m["workloads"] == ["lessac-high.batch.paragraph"]
        assert callable(reader(name))


def test_a_traced_run_of_the_tiny_cell_reports_them():
    """What holds in any window, on a loaded machine too.  Which requests a
    2 s window holds hangs on the machine's load, and with them whether a
    noisy duration clips a row (a rerun, a new bucket, a compile) and
    whether another process adds an entry to the shared compile cache: none
    of that is asserted to be nought."""
    out = run.run_cell("tiny.paragraph", 3000000011, 2.0, True,
                       benchmark_file=BENCH, platform="cpu",
                       require_accelerator=False)
    assert out["correct"] is True and out["failed"] == 0
    got = {k: v["value"] for k, v in out["metrics"].items()}
    assert set(NEW) <= set(got)
    for name in NEW:
        assert isinstance(got[name], float)
    total, ragged, headroom, bucket = (got[n] for n in SHARES)
    # the split of the total into its five causes, the two that a quiet
    # window leaves at nought read by the tests' own readers
    dummy_rows = got["sched.pad_dummy_rows_share.batch"]
    retried = got["sched.pad_retried_share.batch"]
    assert 0.0 < total < 100.0
    assert total == pytest.approx(
        ragged + headroom + bucket + dummy_rows + retried, abs=1e-9)
    assert min(ragged, headroom, bucket, dummy_rows, retried) >= 0.0
    assert got["sched.overflow_retries.batch"] >= 0.0
    assert got["fetch.host_ms_per_dispatch.batch"] > 0.0
    assert got["warmup.cold_compiles_in_window.batch"] >= 0.0
