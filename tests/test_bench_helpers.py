"""Bench harness helpers: these run inside the driver's single recorded
bench invocation, so they get their own coverage here."""

import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

from voices import tiny_voice


def test_prewarm_neighbor_buckets_compiles_adjacent_shapes():
    v = tiny_voice(seed=7)
    v.speak_batch(["ʃɔːt."])  # one key → fewer prewarm compiles
    before = set(v._full_cache)
    v.prewarm_neighbor_buckets()
    added = set(v._full_cache) - before
    assert added, "no neighbor buckets compiled"
    # every added key shares (b, t) with a warmed key and sits one frame
    # bucket away
    from sonata_tpu.utils.buckets import FRAME_BUCKETS

    for (b, t, f) in added:
        neighbors = {
            FRAME_BUCKETS[max(FRAME_BUCKETS.index(wf) - 1, 0)]
            for (wb, wt, wf) in before if (wb, wt) == (b, t)
        } | {
            FRAME_BUCKETS[min(FRAME_BUCKETS.index(wf) + 1,
                              len(FRAME_BUCKETS) - 1)]
            for (wb, wt, wf) in before if (wb, wt) == (b, t)
        }
        assert f in neighbors


def test_bench_device_summary_names_the_platform():
    """Every bench result line carries the device it ran on."""
    from bench import device_summary

    d = device_summary()
    assert d["platform"] == "cpu" and d["device_count"] >= 1
    assert isinstance(d["device_kind"], str) and d["device_kind"]


# ---------------------------------------------------------------------------
# bench_trend waiver mechanics (ISSUE 15): a clean tree exits 0, only
# NEW regressions (or a rotted waiver list) flag
# ---------------------------------------------------------------------------

def _flag(family="F", metric="m_ttfb", from_rev="r01", to_rev="r02",
          pct=50.0):
    return {"family": family, "metric": metric, "from_rev": from_rev,
            "to_rev": to_rev, "from": 1.0, "to": 1.5, "change_pct": pct}


def test_apply_waivers_splits_active_waived_stale():
    from tools.bench_trend import apply_waivers

    flags = [_flag(), _flag(metric="other_ttfb")]
    waivers = [
        {"family": "F", "metric": "m_ttfb", "from_rev": "r01",
         "to_rev": "r02", "reason": "documented host noise"},
        {"family": "F", "metric": "gone_ttfb", "from_rev": "r01",
         "to_rev": "r02", "reason": "stale entry"},
    ]
    active, waived, stale = apply_waivers(flags, waivers)
    assert [f["metric"] for f in active] == ["other_ttfb"]
    assert [w["metric"] for w in waived] == ["m_ttfb"]
    assert waived[0]["reason"] == "documented host noise"
    assert [w["metric"] for w in stale] == ["gone_ttfb"]


def test_apply_waivers_matches_exact_rev_pair_only():
    from tools.bench_trend import apply_waivers

    waivers = [{"family": "F", "metric": "m_ttfb", "from_rev": "r02",
                "to_rev": "r03", "reason": "a different rev pair"}]
    active, waived, stale = apply_waivers([_flag()], waivers)
    assert len(active) == 1 and not waived and len(stale) == 1


def test_load_waivers_rejects_reasonless_entries(tmp_path, monkeypatch):
    import json

    from tools import bench_trend

    import pytest

    bad = tmp_path / "BENCH_WAIVERS.json"
    bad.write_text(json.dumps({"waivers": [
        {"family": "F", "metric": "m", "from_rev": "r01",
         "to_rev": "r02"}]}))
    monkeypatch.setattr(bench_trend, "WAIVERS_PATH", bad)
    with pytest.raises(ValueError, match="reason"):
        bench_trend.load_waivers()


def test_committed_waiver_list_is_clean():
    """The repo's own trend fold must exit clean: every committed flag
    waived with a reason, no stale waivers — the CI lane now blocks on
    exactly this."""
    from tools.bench_trend import (
        apply_waivers,
        collect,
        find_regressions,
        load_waivers,
    )

    active, _waived, stale = apply_waivers(find_regressions(collect()),
                                           load_waivers())
    assert active == [] and stale == []


def test_trend_directions_for_cache_family():
    from tools.bench_trend import direction

    assert direction("zipf_hit_ratio") == "up"
    assert direction("cache_miss_over_hit_speedup") == "up"
    assert direction("cached_replay_ttfb_p50_hit_ms") == "down"
