"""``moe.grouped_step_share.sentence`` and ``.blocks`` (PR 34) on recorded
runs: 100 where every step group says its program ran the grouped kernel,
the steps' share where some do, and nothing on the spans of a program from
before the attribute (the parent's).

The two readers wait for their entries in ``BENCHMARK.json``:
``test_sdar_cell.py`` pins the end of its ``per_layer`` list to
``data/sdar-tiny-benchmark.json``'s, so an entry appended there needs that
file to gain it too, which is a ``benchmark`` PR's to do (PERF.md §7.3l)."""

from pathlib import Path

import pytest

from perfbench.harness import parts

ROOT = Path(__file__).resolve().parents[2]
CELLS = {"moe.grouped_step_share.sentence": "lfm2-24b-a2b.batch.sentence",
         "moe.grouped_step_share.blocks": "sdar-30b-a3b.batch.sentence"}


def group(steps: int, impl=None) -> dict:
    attrs = {"kind": "step", "steps": steps, "slots": 64,
             "live_slot_steps": 63 * steps, "layers": [0, 1],
             "assignments": [252 * steps] * 2,
             "experts_touched": [61 * steps] * 2,
             "max_expert_assignments": [11 * steps] * 2}
    if impl is not None:
        attrs["expert_matmul"] = impl
    return {"name": "dispatch", "start": 10.0, "end": 10.7, "attrs": attrs}


def recorded(*groups) -> dict:
    prefill = {"name": "dispatch", "start": 10.1, "end": 10.12,
               "attrs": {"kind": "prefill", "rows": 1, "tokens": 100,
                         "expert_matmul": "ragged_dot"}}
    return {"spans": [*groups, prefill], "trace": {}}


RUNS = {
    "every_group_grouped": (recorded(group(32, "grouped"),
                                     group(32, "grouped")), 100.0),
    "every_group_ragged_dot": (recorded(group(32, "ragged_dot"),
                                        group(20, "ragged_dot")), 0.0),
    "by_steps_not_by_groups": (recorded(group(32, "grouped"),
                                        group(8, "ragged_dot")), 80.0),
    "the_parents_spans": (recorded(group(32), group(32)), None),
    "only_the_groups_that_say": (recorded(group(32), group(16, "grouped")),
                                 100.0),
    "no_span_at_all": ({"spans": [], "trace": {}}, None),
    "no_spans_key": ({}, None),
}


@pytest.mark.parametrize("case", sorted(RUNS))
@pytest.mark.parametrize("name", sorted(CELLS))
def test_the_grouped_share_on_a_recorded_run(name, case):
    read = parts.load_reader(ROOT, ["perfbench"], name)
    run, want = RUNS[case]
    got = read(run)
    assert got is None if want is None else got == pytest.approx(want)
